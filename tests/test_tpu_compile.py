"""Compile-only checks for a described TPU v5e chip (no chip attached).

Each test lowers and compiles with ``interpret=False`` for one device of a
described ``v5e:2x2`` topology: the Pallas kernels at the sizes
``chip_smoke.py`` runs (small shapes get a (1, 128) tiling that hides
unaligned slices), and qwen2-1.5b's serving steps at published widths, cut
to 2 layers (widths, not depth, decide tiling).  Nothing runs, so nothing
here says a result is right: ``chip_smoke.py`` on the chip does that.

The topology is described inside module-scoped fixtures, never at import,
so every test worker collects the same tests and only the worker given
this file loads the TPU compiler.
"""
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
import chip_smoke  # noqa: E402

KERNELS = ("stream", "hotspot", "pathfinder", "nw", "lud", "matmul",
           "flash_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one, so keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    make, call, _ = chip_smoke._kernel_cases()[kernel]
    args = _on(one_chip, jax.eval_shape(make))
    # strategy None: the kernel's default strategy
    compiled = jax.jit(lambda a: call(a, None)).lower(args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def qwen_2_layers(one_chip):
    from repro.configs import get_config
    from repro.distributed.sharding import split_tree
    from repro.models import build_model
    cfg = dataclasses.replace(get_config("qwen2-1.5b"), n_layers=2)
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: split_tree(model.init(k))[0],
                            jax.random.PRNGKey(0))
    return cfg, model, _on(one_chip, params)


def _paged(cfg, one_chip, n_blocks=64, block_len=16):
    from repro.models import transformer as tfm
    return _on(one_chip, jax.eval_shape(
        lambda: tfm.init_paged_state(cfg, n_blocks, block_len)))


def _i32(one_chip, *shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)


def test_paged_decode_step_compiles_for_v5e(qwen_2_layers, one_chip,
                                            no_persistent_cache):
    cfg, model, params = qwen_2_layers
    paged = _paged(cfg, one_chip)
    lowered = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
        params, paged, _i32(one_chip, 4, 1), _i32(one_chip, 4, 36),
        _i32(one_chip, 4))
    assert lowered.out_info[0].shape == (4, cfg.vocab)
    lowered.compile()


def test_paged_decode_step_contracts_grouped_heads_for_v5e(
        qwen_2_layers, one_chip, no_persistent_cache):
    """At the decode cell's batch and view (128 slots x 65 blocks of 16 rows,
    an arena of 128 x 1,040 rows plus the scratch block) the step contracts
    each KV head with its 6 query heads: the layer scan holds no head-repeat
    ``jnp.take`` of the view (it lowers to per-head copy loops into a
    materialised (128, 1040, 12, 128) tensor per layer; the token embedding's
    ``jnp.take`` lies outside the scan), and temporaries stay under 400 MB
    (775 MB with the gather)."""
    cfg, model, params = qwen_2_layers
    b, mb, bl = 128, 65, 16
    paged = _paged(cfg, one_chip, n_blocks=b * mb + 1, block_len=bl)
    compiled = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
        params, paged, _i32(one_chip, b, 1), _i32(one_chip, b, mb),
        _i32(one_chip, b)).compile()
    assert not re.search(r'op_name="[^"]*/while/[^"]*jit\(_take\)',
                         compiled.as_text())
    assert compiled.memory_analysis().temp_size_in_bytes < 400e6


def test_prefill_chunk_compiles_for_v5e(qwen_2_layers, one_chip,
                                        no_persistent_cache):
    cfg, model, params = qwen_2_layers
    paged = _paged(cfg, one_chip)
    lowered = jax.jit(model.prefill_chunk, donate_argnums=(1,)).lower(
        params, paged, _i32(one_chip, 1, 128), _i32(one_chip, 1, 36),
        _i32(one_chip), _i32(one_chip))
    assert lowered.out_info[0].shape == (1, cfg.vocab)
    lowered.compile()


@pytest.fixture(scope="module")
def deepseek_2_layers(one_chip):
    """deepseek-v2-lite at published widths, its leading dense layer and
    one expert layer, holding 8 of the 64 routed experts."""
    from repro.configs import get_config
    from repro.distributed.sharding import split_tree
    from repro.models import build_model
    base = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(base, n_layers=2, moe=dataclasses.replace(
        base.moe, n_held=8))
    model = build_model(cfg)
    params = jax.eval_shape(lambda k: split_tree(model.init(k))[0],
                            jax.random.PRNGKey(0))
    return cfg, model, _on(one_chip, params)


def test_latent_paged_steps_compile_for_v5e(deepseek_2_layers, one_chip,
                                            no_persistent_cache):
    """Absorbed decode at batch 64 over 1,088-block views, and a 512-row
    chunk, over a latent arena of one 576-wide row a token a layer, held
    640 wide."""
    cfg, model, params = deepseek_2_layers
    paged = _paged(cfg, one_chip, n_blocks=2048)
    assert paged.k.shape == (2, 2048, 16, 1, 640) and paged.v.shape[-1] == 0
    decode = jax.jit(model.decode_paged, donate_argnums=(1,)).lower(
        params, paged, _i32(one_chip, 64, 1), _i32(one_chip, 64, 1088),
        _i32(one_chip, 64))
    assert decode.out_info[0].shape == (64, cfg.vocab)
    assert decode.out_info[1].picks.shape == (1, 64)
    decode.compile()
    chunk = jax.jit(model.prefill_chunk, donate_argnums=(1,)).lower(
        params, paged, _i32(one_chip, 1, 512), _i32(one_chip, 1, 1088),
        _i32(one_chip), _i32(one_chip))
    assert chunk.out_info[0].shape == (1, cfg.vocab)
    chunk.compile()
