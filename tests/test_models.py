"""Model-stack correctness: chunked-vs-stepwise recurrence equivalence,
chunked attention vs the naive oracle, MoE routing invariants, and the
end-to-end decode == teacher-forced-forward consistency check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.config import ArchConfig, AttnConfig, MoEConfig, SSMConfig
from repro.distributed.sharding import split_tree
from repro.kernels import ref
from repro.models import build_model
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm


def key(i):
    return jax.random.PRNGKey(i)


# ---------------------------------------------------------------------------
# chunked attention == oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 16)])
@pytest.mark.parametrize("h,kvh", [(4, 2), (6, 2), (4, 4)])
def test_attend_chunked_vs_oracle(causal, window, h, kvh):
    b, s, d = 2, 64, 16
    ks = jax.random.split(key(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kvh, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kvh, d), jnp.float32)
    idx = attn.kv_index_map(h, kvh, h)
    got = attn.attend_chunked(q, k, v, idx, causal=causal, window=window,
                              chunk=16)
    for bi in range(b):
        qh = q[bi].transpose(1, 0, 2)
        kh = jnp.repeat(k[bi].transpose(1, 0, 2), h // kvh, axis=0)
        vh = jnp.repeat(v[bi].transpose(1, 0, 2), h // kvh, axis=0)
        want = ref.attention_ref(qh, kh, vh, causal=causal, window=window)
        np.testing.assert_allclose(got[bi].transpose(1, 0, 2), want,
                                   rtol=2e-5, atol=2e-5)


def test_attend_chunked_head_padding_exact():
    """Padded q heads must not change the real heads' outputs."""
    b, s, d, h, kvh = 1, 32, 8, 3, 1
    ks = jax.random.split(key(1), 3)
    q = jax.random.normal(ks[0], (b, s, h, d))
    k = jax.random.normal(ks[1], (b, s, kvh, d))
    v = jax.random.normal(ks[2], (b, s, kvh, d))
    base = attn.attend_chunked(q, k, v, attn.kv_index_map(h, kvh, h),
                               causal=True, window=0, chunk=8)
    q_pad = jnp.concatenate([q, jnp.zeros((b, s, 2, d))], axis=2)
    padded = attn.attend_chunked(q_pad, k, v, attn.kv_index_map(h, kvh, h + 2),
                                 causal=True, window=0, chunk=8)
    np.testing.assert_allclose(padded[:, :, :h], base, rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# decode / prefix attention over a cache view: grouped heads == head gather
# ---------------------------------------------------------------------------

GQA = [(12, 2), (6, 2), (4, 2), (4, 4)]


def _view_case(fn, dtype, h, kvh, q_heads, seed=0):
    """Inputs for ``attend_decode`` (C=1) or ``attend_prefix`` (C=5) over a
    ragged (B=3, W=24) view: row 0 holds 24 positions, row 1 holds 9 with
    -1 rows after them, row 2 is empty."""
    b, w, d = 3, 24, 16
    c = 1 if fn == "decode" else 5
    ks = jax.random.split(key(seed), 3)
    q = jax.random.normal(ks[0], (b, c, q_heads, d)).astype(dtype)
    k = jax.random.normal(ks[1], (b, w, kvh, d)).astype(dtype)
    v = jax.random.normal(ks[2], (b, w, kvh, d)).astype(dtype)
    pos = np.full((b, w), -1, np.int32)
    pos[0] = np.arange(w)
    pos[1, :9] = np.arange(9)
    last = np.asarray([w - 1, 8, 0], np.int32)
    q_pos = last[:, None] - np.arange(c - 1, -1, -1, dtype=np.int32)

    def call(idx_map, qq=q):
        if fn == "decode":
            return attn.attend_decode(qq, k, v, jnp.asarray(pos), idx_map,
                                      q_position=jnp.asarray(q_pos[:, 0]))
        return attn.attend_prefix(qq, k, v, jnp.asarray(pos), idx_map,
                                  q_positions=jnp.asarray(q_pos))
    return q, call


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("fn", ["decode", "prefix"])
@pytest.mark.parametrize("h,kvh", GQA)
def test_grouped_view_attention_matches_head_gather(h, kvh, fn, dtype):
    """The grouped contraction equals the head-gather path, which a padded
    head map takes: its first h heads are the same heads over the same
    KV heads.  Exact in bf16, within 1e-6 in fp32."""
    q, call = _view_case(fn, dtype, h, kvh, h)
    assert attn.group_size(attn.kv_index_map(h, kvh, h), kvh) == h // kvh
    grouped = call(attn.kv_index_map(h, kvh, h))
    q_pad = jnp.concatenate([q, jnp.zeros(q.shape[:2] + (2, q.shape[3]),
                                          q.dtype)], axis=2)
    gathered = call(attn.kv_index_map(h, kvh, h + 2), q_pad)[:, :, :h]
    assert grouped.dtype == gathered.dtype == dtype
    if dtype == jnp.bfloat16:
        np.testing.assert_array_equal(np.asarray(grouped.astype(jnp.float32)),
                                      np.asarray(gathered.astype(jnp.float32)))
    else:
        np.testing.assert_allclose(grouped, gathered, rtol=0, atol=1e-6)


def _primitives(jaxpr):
    """Every primitive in ``jaxpr``, nested jaxprs (``jnp.take``'s jit) too."""
    names = set()
    for e in jaxpr.eqns:
        names.add(e.primitive.name)
        for sub in jax.core.jaxprs_in_params(e.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("fn", ["decode", "prefix"])
@pytest.mark.parametrize("h,kvh,h_pad", [c + (c[0],) for c in GQA]
                         + [(4, 2, 6), (12, 2, 14)])
def test_view_attention_gathers_heads_only_for_padded_maps(h, kvh, h_pad, fn):
    """The head gather is chosen from the static head map: a plain grouping
    traces no gather, a padded map (clamped to the last KV head) does."""
    q, call = _view_case(fn, jnp.float32, h, kvh, h_pad)
    idx_map = attn.kv_index_map(h, kvh, h_pad)
    prims = _primitives(jax.make_jaxpr(lambda qq: call(idx_map, qq))(q).jaxpr)
    padded = h_pad != h
    assert ("gather" in prims) == padded
    assert (attn.group_size(idx_map, kvh) is None) == padded


# ---------------------------------------------------------------------------
# recurrent blocks: chunkwise == stepwise
# ---------------------------------------------------------------------------

def test_mlstm_chunkwise_equals_stepwise():
    B, S, H, dh = 2, 32, 2, 8
    ks = jax.random.split(key(2), 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, dh)) for i in range(3))
    i_raw = jax.random.normal(ks[3], (B, S, H))
    f_raw = jax.random.normal(ks[4], (B, S, H)) + 2.0
    st0 = ssm.mlstm_state_init(B, H, dh)
    h_chunk, st_c = ssm.mlstm_seq(q, k, v, i_raw, f_raw, st0, chunk=8)
    st = st0
    outs = []
    for t in range(S):
        h, st = ssm.mlstm_step(q[:, t], k[:, t], v[:, t], i_raw[:, t],
                               f_raw[:, t], st)
        outs.append(h)
    np.testing.assert_allclose(h_chunk, jnp.stack(outs, 1), rtol=2e-4,
                               atol=2e-4)
    np.testing.assert_allclose(st_c.c, st.c, rtol=2e-4, atol=2e-4)


def test_mlstm_chunk_size_invariance():
    B, S, H, dh = 1, 24, 2, 4
    ks = jax.random.split(key(3), 5)
    q, k, v = (jax.random.normal(ks[i], (B, S, H, dh)) for i in range(3))
    i_raw = jax.random.normal(ks[3], (B, S, H))
    f_raw = jax.random.normal(ks[4], (B, S, H)) + 1.0
    st0 = ssm.mlstm_state_init(B, H, dh)
    h1, _ = ssm.mlstm_seq(q, k, v, i_raw, f_raw, st0, chunk=4)
    h2, _ = ssm.mlstm_seq(q, k, v, i_raw, f_raw, st0, chunk=12)
    np.testing.assert_allclose(h1, h2, rtol=2e-4, atol=2e-4)


def test_mamba_chunkwise_equals_stepwise():
    cfg = ArchConfig(name="t", family="hybrid", n_layers=1, d_model=16,
                     n_heads=2, n_kv_heads=1, d_ff=32, vocab=64,
                     ssm=SSMConfig(kind="mamba", d_state=4, chunk=8))
    p, _ = split_tree(ssm.mamba_init(key(4), cfg, d_inner=32))
    B, S = 2, 32
    x = jax.random.normal(key(5), (B, S, 16))
    st0 = ssm.mamba_state_init(B, 32, 4)
    y_seq, st_seq = ssm.mamba_apply(p, x, cfg, st0, mode="train",
                                    compute_dtype=jnp.float32)
    ys, st = [], st0
    for t in range(S):
        y, st = ssm.mamba_apply(p, x[:, t:t + 1], cfg, st, mode="decode",
                                compute_dtype=jnp.float32)
        ys.append(y)
    np.testing.assert_allclose(y_seq, jnp.concatenate(ys, 1), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(st_seq.s, st.s, rtol=2e-3, atol=2e-3)


def test_slstm_seq_equals_stepwise():
    cfg = ArchConfig(name="t", family="ssm", n_layers=1, d_model=16,
                     n_heads=2, n_kv_heads=2, d_ff=0, vocab=64)
    p, _ = split_tree(ssm.slstm_init(key(6), cfg, n_heads=2))
    B, S = 2, 16
    x = jax.random.normal(key(7), (B, S, 16))
    st0 = ssm.slstm_state_init(B, 2, 8)
    out, _ = ssm.slstm_block(p, x, cfg, st0, mode="train", n_heads=2,
                             compute_dtype=jnp.float32)
    outs, st = [], st0
    for t in range(S):
        o, st = ssm.slstm_block(p, x[:, t:t + 1], cfg, st, mode="decode",
                                n_heads=2, compute_dtype=jnp.float32)
        outs.append(o)
    np.testing.assert_allclose(out, jnp.concatenate(outs, 1), rtol=2e-4,
                               atol=2e-4)


# ---------------------------------------------------------------------------
# MoE invariants
# ---------------------------------------------------------------------------

def _moe_cfg(**kw):
    base = dict(name="t", family="moe", n_layers=1, d_model=32, n_heads=2,
                n_kv_heads=2, d_ff=0, vocab=64,
                moe=MoEConfig(n_experts=8, top_k=2, n_shared=0,
                              d_ff_expert=16, capacity_factor=2.0))
    base.update(kw)
    return ArchConfig(**base)


def test_moe_output_finite_and_grad():
    cfg = _moe_cfg()
    p, _ = split_tree(moe_mod.moe_init(key(8), cfg))
    x = jax.random.normal(key(9), (2, 16, 32))
    out, aux, _ = moe_mod.moe_apply(p, x, cfg, compute_dtype=jnp.float32)
    assert out.shape == x.shape
    assert bool(jnp.isfinite(out).all()) and bool(jnp.isfinite(aux))
    g = jax.grad(lambda pp: moe_mod.moe_apply(pp, x, cfg,
                                              jnp.float32)[0].sum())(p)
    assert sum(float(jnp.abs(t).sum()) for t in jax.tree.leaves(g)) > 0


def test_moe_aux_loss_balanced_router_is_one():
    """With perfectly uniform routing the Switch aux loss equals 1."""
    cfg = _moe_cfg()
    p, _ = split_tree(moe_mod.moe_init(key(10), cfg))
    p["router"]["w"] = jnp.zeros_like(p["router"]["w"])  # uniform probs
    x = jax.random.normal(key(11), (4, 16, 32))
    _, aux, _ = moe_mod.moe_apply(p, x, cfg, compute_dtype=jnp.float32)
    assert abs(float(aux) - 1.0) < 0.05


def test_moe_capacity_drops_tokens():
    """With capacity_factor -> tiny, most tokens are dropped: output norm
    shrinks but stays finite."""
    cfg_big = _moe_cfg()
    cfg_small = _moe_cfg(moe=MoEConfig(n_experts=8, top_k=2, n_shared=0,
                                       d_ff_expert=16, capacity_factor=0.1))
    p, _ = split_tree(moe_mod.moe_init(key(12), cfg_big))
    x = jax.random.normal(key(13), (2, 64, 32))
    out_big, _, _ = moe_mod.moe_apply(p, x, cfg_big,
                                      compute_dtype=jnp.float32)
    out_small, _, _ = moe_mod.moe_apply(p, x, cfg_small,
                                        compute_dtype=jnp.float32)
    assert float(jnp.linalg.norm(out_small)) < float(jnp.linalg.norm(out_big))
    assert bool(jnp.isfinite(out_small).all())


# ---------------------------------------------------------------------------
# decode == teacher-forced forward (end-to-end, per family)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family_kw", [
    dict(family="dense"),
    dict(family="hybrid", ssm=SSMConfig(kind="mamba", d_state=4, chunk=8),
         attn=AttnConfig(kind="sliding", window=8, chunk=8)),
    dict(family="ssm", d_ff=0, n_kv_heads=4,
         attn=AttnConfig(kind="none"),
         ssm=SSMConfig(kind="xlstm", slstm_every=2, chunk=8)),
], ids=["dense", "hybrid", "ssm"])
def test_decode_matches_forward(family_kw):
    base = dict(name="t", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                d_ff=64, vocab=128, attn=AttnConfig(chunk=8))
    base.update(family_kw)
    cfg = ArchConfig(**base)
    model = build_model(cfg)
    params, _ = split_tree(model.init(key(14)))
    B, S, EXTRA = 2, 16, 4
    toks = jax.random.randint(key(15), (B, S + EXTRA), 0, cfg.vocab)
    # teacher-forced forward over the full sequence
    full = model.forward(params, {"tokens": toks,
                                  "labels": jnp.zeros_like(toks)})
    # prefill on the prefix, decode the rest one token at a time.
    # tolerance: the model path is bf16 (matmuls at input dtype with fp32
    # accumulation), and decode/chunked paths sum in different orders
    logits, state = model.prefill(params, {"tokens": toks[:, :S]},
                                  budget=S + EXTRA)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(full[:, S - 1]), rtol=6e-2,
                               atol=6e-2)
    for t in range(EXTRA):
        logits, state = model.decode_step(params, state, toks[:, S + t:S + t + 1])
        np.testing.assert_allclose(np.asarray(logits),
                                   np.asarray(full[:, S + t]), rtol=6e-2,
                                   atol=6e-2)
