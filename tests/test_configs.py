"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
config of the same family and runs one forward + one train step on CPU,
asserting output shapes and the absence of NaNs.  Full configs are exercised
only via the dry-run (ShapeDtypeStruct, no allocation)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.core.config import RunConfig
from repro.distributed.sharding import split_tree
from repro.launch.train import build_train_step, set_param_axes
from repro.models import build_model
from repro.optim import adamw_init

B, S = 2, 32


def make_batch(cfg, b=B, s=S, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    n_text = s - (cfg.n_patches or 0)
    if cfg.is_encdec:
        n_text = s // 2
    batch = {
        "tokens": jax.random.randint(ks[0], (b, n_text), 0, cfg.vocab),
        "labels": jax.random.randint(ks[1], (b, n_text), 0, cfg.vocab),
    }
    if cfg.n_patches:
        batch["patches"] = jax.random.normal(
            ks[2], (b, cfg.n_patches, cfg.d_model), jnp.float32)
    if cfg.is_encdec:
        batch["frames"] = jax.random.normal(
            ks[2], (b, s - n_text, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward_and_train_step(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params_ann = model.init(jax.random.PRNGKey(0))
    params, axes = split_tree(params_ann)
    batch = make_batch(cfg)

    # forward: logits shape + finite
    logits = jax.jit(model.forward)(params, batch)
    n_pos = batch["tokens"].shape[1] + (cfg.n_patches or 0)
    assert logits.shape[0] == B and logits.shape[1] == n_pos
    assert logits.shape[2] >= cfg.vocab
    assert bool(jnp.isfinite(logits[..., :cfg.vocab]).all()), arch

    # one full train step (grads + adamw update): params change, no NaNs
    set_param_axes(axes)
    run = RunConfig(microbatches=2, zero1=False, total_steps=10,
                    warmup_steps=2)
    step = jax.jit(build_train_step(model, run))
    opt = adamw_init(params)
    new_params, new_opt, metrics = step(params, opt, batch,
                                        jnp.zeros((), jnp.int32))
    assert bool(jnp.isfinite(metrics["ce"])), arch
    assert float(metrics["ce"]) > 0
    deltas = jax.tree.map(lambda a, b_: float(jnp.abs(a - b_).max()),
                          params, new_params)
    assert max(jax.tree.leaves(deltas)) > 0, "params did not move"
    for leaf in jax.tree.leaves(new_params):
        assert bool(jnp.isfinite(leaf).all()), arch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_prefill_decode(arch):
    cfg = get_smoke_config(arch)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    batch = make_batch(cfg)
    logits, state = jax.jit(model.prefill)(params, batch)
    assert bool(jnp.isfinite(logits[..., :cfg.vocab]).all()), arch
    toks = jnp.argmax(logits[..., :cfg.vocab], axis=-1)[:, None]
    logits2, state2 = jax.jit(model.decode_step)(params, state,
                                                 toks.astype(jnp.int32))
    assert logits2.shape[0] == B
    assert bool(jnp.isfinite(logits2[..., :cfg.vocab]).all()), arch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact published dimensions."""
    cfg = get_config(arch)
    expected = {
        "command-r-35b": (40, 8192, 64, 8, 22528, 256000),
        "deepseek-67b": (95, 8192, 64, 8, 22016, 102400),
        "deepseek-v2-lite": (27, 2048, 16, 16, 10944, 102400),
        "phi3-mini-3.8b": (32, 3072, 32, 32, 8192, 32064),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "phi-3-vision-4.2b": (32, 3072, 32, 32, 8192, 32064),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 0, 151936),
        "qwen2-moe-a2.7b": (24, 2048, 16, 16, 0, 151936),
        "whisper-medium": (24, 1024, 16, 16, 4096, 51865),
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
    }[arch]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
           cfg.d_ff, cfg.vocab)
    assert got == expected, (arch, got, expected)


def test_moe_configs():
    q3 = get_config("qwen3-moe-235b-a22b")
    assert (q3.moe.n_experts, q3.moe.top_k, q3.moe.n_shared,
            q3.moe.d_ff_expert) == (128, 8, 0, 1536)
    q2 = get_config("qwen2-moe-a2.7b")
    assert (q2.moe.n_experts, q2.moe.top_k, q2.moe.n_shared,
            q2.moe.d_ff_expert) == (60, 4, 4, 1408)


def test_param_counts_in_published_ballpark():
    """Analytic param counts should be within ~25% of the published sizes."""
    targets = {
        "command-r-35b": 30e9,   # assigned GQA-kv8 config of the 35b family
        "deepseek-67b": 67e9,
        "phi3-mini-3.8b": 3.8e9, "qwen2-1.5b": 1.5e9,
        # the assigned 48L/d2048/pf2 config lands at ~2B; the "1.3b" label
        # is the published family name (DESIGN.md §6)
        "xlstm-1.3b": 2.0e9,
        "qwen3-moe-235b-a22b": 235e9,
        "hymba-1.5b": 1.5e9,
    }
    for arch, target in targets.items():
        got = get_config(arch).param_count()
        assert 0.7 * target < got < 1.35 * target, \
            (arch, got / 1e9, target / 1e9)
