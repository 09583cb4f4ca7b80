"""DeepSeek-V2's latent attention and one chip's share of its experts, at
the smoke size (1 dense + 2 expert layers, 8 experts top-2, 4 held), on
seeded weights, against the plain float32 reference that the benchmark
checks the chip with (``chipbench/models/deepseek_v2.py``)."""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed.sharding import split_tree
from repro.launch.serve import ServingLoop
from repro.models import attention as attn
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.layers import yarn_inv_freq
from repro.obs.trace import get_tracer
from repro.serve import Request

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = get_config("deepseek-v2-lite", smoke=True)


@pytest.fixture(scope="module")
def ref():
    sys.path.insert(0, CHECKOUT)
    from chipbench.harness import Manifest
    return Manifest().module("models", "deepseek_v2")


@pytest.fixture(scope="module")
def tiny():
    with open(os.path.join(CHECKOUT, "chipbench", "tests", "data", "configs",
                           "tiny-mla.json")) as f:
        return json.load(f)


def test_published_parameter_count():
    """15.7B as published (DeepSeek-V2-Lite's model card), within 0.5%."""
    n = get_config("deepseek-v2-lite").param_count()
    assert abs(n / 15.7e9 - 1) < 0.005, n


def test_yarn_frequencies_and_softmax_scale_follow_the_formulas():
    """theta 1e4, factor 40 over 4096 positions, beta_fast 32, beta_slow 1,
    both mscales 0.707, on 64 RoPE columns."""
    cfg = get_config("deepseek-v2-lite")
    dim, base = 64, 10000.0
    turns = [dim * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(base))
             for r in (32, 1)]
    lo, hi = max(math.floor(turns[0]), 0), min(math.ceil(turns[1]), dim - 1)
    want = []
    for i in range(dim // 2):
        plain = base ** (-2 * i / dim)
        ramp = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        want.append(plain / 40 * ramp + plain * (1 - ramp))
    got = attn.mla_rope_freq(cfg)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert (lo, hi) == (10, 23)
    np.testing.assert_allclose(got[:lo], [base ** (-2 * i / dim)
                                          for i in range(lo)], rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert attn.mla_scale(cfg) == pytest.approx(192 ** -0.5 * m * m,
                                                rel=1e-12)
    np.testing.assert_array_equal(
        yarn_inv_freq(dim, base, 40.0, 4096, 32.0, 1.0), got)


def test_absorbed_decode_equals_decompressed_attention():
    """Decode absorbs wkv_b into the query and the output; a one-row chunk
    decompresses the latent.  In float32 they agree to rounding."""
    cfg = dataclasses.replace(SMOKE, dtype="float32")
    p, _ = split_tree(attn.mla_init(jax.random.PRNGKey(1), cfg))
    b, w = 3, 40
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    a = cfg.attn
    q_nope = jax.random.normal(ks[0], (b, 1, 4, a.qk_nope_dim))
    q_pe = jax.random.normal(ks[1], (b, 1, 4, a.qk_rope_dim))
    view = jax.random.normal(ks[2], (b, w, 1, a.kv_lora_rank + a.qk_rope_dim))
    pos = jnp.where(jnp.arange(w)[None] < jnp.array([[5], [40], [23]]),
                    jnp.arange(w)[None], -1)
    q_at = jnp.array([4, 39, 22], jnp.int32)
    with jax.default_matmul_precision("highest"):
        absorbed = attn.mla_attend_decode(p, q_nope, q_pe, view, pos, cfg,
                                          q_at, jnp.float32)
        full = attn.mla_attend_prefix(p, q_nope, q_pe, view, pos, cfg,
                                      q_at[:, None], jnp.float32)
    assert absorbed.shape == full.shape == (b, 1, 4, a.v_head_dim)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(full),
                               rtol=1e-4, atol=1e-5)


def _moe_layer(cfg, key=3):
    p, _ = split_tree(moe_mod.moe_init(jax.random.PRNGKey(key), cfg))
    return p


def _share(p, cfg, lo, n):
    """The layer one chip of the deployment holds: experts [lo, lo + n)."""
    share = dict(p, **{k: p[k][lo:lo + n]
                       for k in ("w_gate", "w_up", "w_down")})
    return share, dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, expert_lo=lo, n_held=n))


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["gate"]["w"]) * (h @ m["up"]["w"])) @ \
        m["down"]["w"]


def test_held_shares_add_up_to_the_uncut_layer():
    """Two chips of 4 experts each: their parts, with the shared expert
    (which every chip computes) counted once, give the whole layer."""
    whole = dataclasses.replace(SMOKE, dtype="float32",
                                moe=dataclasses.replace(SMOKE.moe, n_held=0))
    p = _moe_layer(whole)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 24, whole.d_model))
    with jax.default_matmul_precision("highest"):
        full, _, picks = moe_mod.moe_apply(p, x, whole, jnp.float32)
        parts = [moe_mod.moe_apply(sp, x, sc, jnp.float32)
                 for sp, sc in (_share(p, whole, lo, 4) for lo in (0, 4))]
        shared = _swiglu(x, p["shared"])
    np.testing.assert_allclose(
        np.asarray(parts[0][0] + parts[1][0] - shared), np.asarray(full),
        rtol=1e-5, atol=1e-5)
    assert int(picks.sum()) == 2 * 24 * whole.moe.top_k
    for _, _, pk in parts:     # every chip sees the router's whole choice
        np.testing.assert_array_equal(np.asarray(pk), np.asarray(picks))


def test_forced_one_expert_routing_drops_nothing():
    """Every token routed to experts 0 and 1: with no capacity each expert
    computes all 48 tokens and the layer equals the per-token sum; the
    capacity-bound layer would drop most of them."""
    cfg = dataclasses.replace(SMOKE, dtype="float32")
    p = _moe_layer(cfg)
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"]).at[:, 0].set(10.0)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (3, 16, 64)))
    with jax.default_matmul_precision("highest"):
        out, _, picks = moe_mod.moe_apply(p, x, cfg, jnp.float32)
        h = x.reshape(-1, 64)
        probs = jax.nn.softmax(h @ p["router"]["w"], -1)
        want = sum(probs[:, e:e + 1] * _swiglu(h, {
            "gate": {"w": p["w_gate"][e]}, "up": {"w": p["w_up"][e]},
            "down": {"w": p["w_down"][e]}}) for e in (0, 1))
        want = want + _swiglu(h, p["shared"])
        capped = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, capacity_factor=1.25))
        dropped, _, _ = moe_mod.moe_apply(p, x, capped, jnp.float32)
    assert list(np.asarray(picks)) == [48, 48, 0, 0, 0, 0, 0, 0]
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 64),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    assert not np.allclose(np.asarray(dropped), np.asarray(out), atol=1e-3)


def _requests(seed=0):
    """Three prompts sharing a 40-token prefix, arriving apart so that the
    later two find it cached."""
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, 512, 40).astype(np.int32)
    return [Request(uid=i, prompt=np.concatenate(
        [pre, rng.integers(0, 512, n).astype(np.int32)]), max_new=6,
        arrival=3.0 * i, logits=[]) for i, n in enumerate((5, 9, 3))]


@pytest.mark.parametrize("chunk", [None, 16])
def test_paged_serving_matches_the_reference(ref, tiny, chunk):
    """Monolithic prefill (chunk None), or 16-token chunks with the prefix
    cache, then paged decode; each served logits row against the
    reference's full forward over prompt and served tokens.

    Tolerance: the program computes in bfloat16 (8 bits of mantissa), so
    each layer's output carries relative rounding of about 2^-8; over 3
    layers and the unembedding the served logits differ by 2.3 to 2.5% of
    the largest reference logit on the CPU.  4% leaves room above that;
    rounding every matmul's inputs to float8_e4m3fn instead (the control)
    moves them by 26 to 56%, and fails it."""
    w = ref.make_weights(tiny, jax.random.PRNGKey(0))
    want = jax.eval_shape(lambda k: split_tree(build_model(SMOKE).init(k))[0],
                          jax.random.PRNGKey(0))
    assert jax.tree.map(lambda a: a.shape, want) == \
        jax.tree.map(lambda a: a.shape, w)
    loop = ServingLoop(SMOKE, w, batch=2, max_new=6, block_len=8, max_seq=96,
                       chunk_tokens=chunk, prefix_cache=chunk is not None)
    reqs = _requests()
    loop.run(reqs, max_steps=6)
    if chunk:
        assert loop.scheduler.cache.hit_tokens == 64
    for r in reqs:
        toks, plen = list(r.out_tokens), len(r.prompt)
        assert len(toks) == 6
        seq = jnp.asarray(np.concatenate([r.prompt, toks[:-1]]), jnp.int32)
        at = jnp.arange(plen - 1, plen + 5)
        lg = np.asarray(ref.forward(w, tiny, seq, at))
        ctl = np.asarray(ref.forward(w, tiny, seq, at, fp8=True))
        got = np.stack([np.asarray(x)[:512] for x in r.logits])
        tol = 0.04 * np.abs(lg).max()
        assert np.abs(got - lg).max() < tol, (r.uid, np.abs(got - lg).max())
        assert np.abs(ctl - lg).max() > tol
        # the served token is the program's own greedy pick
        np.testing.assert_array_equal(got.argmax(-1), toks)


def test_routing_counters_record_only_while_the_tracer_records():
    cfg = SMOKE
    params, _ = split_tree(build_model(cfg).init(jax.random.PRNGKey(7)))

    def serve(trace):
        loop = ServingLoop(cfg, params, batch=2, max_new=4, block_len=8,
                           max_seq=64, chunk_tokens=16)
        tracer = get_tracer()
        if trace:
            tracer.enable()
        try:
            loop.run(_requests(1)[:2], max_steps=4)
        finally:
            tracer.disable()
            tracer.clear()
        return {r["name"]: r for r in loop.metrics.snapshot()}

    assert not any(n.startswith("moe.") for n in serve(False))
    m = serve(True)
    # chunks: 45 and 49 prompt rows in 16-row chunks (widths 16, 16, 16
    # and 16, 16, 16, 1); decode: 3 tokens a request, in steps of batch 2;
    # 2 expert layers, top-2 of 8, 4 held, each computing every row
    tokens = 45 + 49 + 2 * 3
    assert m["moe.assignments"]["value"] == tokens * 2 * 2
    assert 0 < m["moe.assignments_here"]["value"] < tokens * 2 * 2
    decode_rows = m["moe.expert_rows"]["value"] / (2 * 4) - 97
    assert decode_rows % 2 == 0 and decode_rows >= 2 * 3
    assert m["moe.max_expert_load"]["count"] == 2 * (7 + decode_rows / 2)
