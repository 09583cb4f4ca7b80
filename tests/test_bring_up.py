"""CPU checks of what running on the chip needs: the interpret default, the
device-kind lookup, the entry points' configs, the compile-cache placement,
``chip_smoke.py`` refusing a CPU, and the kernel repairs at shapes the
interpreter can afford (lanes and rows that are not tile multiples)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bench import runner
from repro.configs import get_config
from repro.core import Strategy, compile_cache, hardware
from repro.kernels import lud, ops, ref
from repro.launch import train
from repro.tuning.search_space import SearchSpace

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_interpret_default_resolves_to_interpreter_on_cpu():
    assert jax.default_backend() == "cpu"
    assert ops.resolve_interpret() is True
    assert ops.resolve_interpret(False) is False
    assert runner.RunOptions().interpret is True
    assert runner.RunOptions(interpret=False).interpret is False


@pytest.mark.parametrize("kind,chip", [("TPU v5 lite", "TPUv5e"),
                                       ("TPU v4", "TPUv4")])
def test_device_kind_lookup(kind, chip):
    assert hardware.chip_for_device_kind(kind).name == chip


@pytest.mark.parametrize("kind", ["TPU v9 ultra", "cpu", ""])
def test_device_kind_lookup_raises_for_unknown_kind(kind):
    with pytest.raises(KeyError, match="unknown device_kind"):
        hardware.chip_for_device_kind(kind)


def test_train_without_smoke_picks_published_config():
    args = train.parse_args([])
    assert args.smoke is False
    cfg = get_config(args.arch, smoke=args.smoke)
    assert cfg == get_config("qwen2-1.5b")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (28, 1536, 151936)
    smoke = train.parse_args(["--smoke"])
    assert get_config(smoke.arch, smoke=smoke.smoke).n_layers == 2


def test_chip_smoke_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_compile_cache_defaults_to_fixed_repo_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.use_persistent_cache()
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    ignored = open(os.path.join(ROOT, ".gitignore")).read().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_leaves_env_dir_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was


# --- kernel repairs at unaligned shapes (interpret mode) --------------------

@pytest.mark.parametrize("strategy", [Strategy.DROP_OFF, Strategy.OVERLAP])
def test_pathfinder_pads_rows_and_lanes(strategy):
    # rows-1 = 20 is not a multiple of tile_rows, cols not of 128
    wall = jax.random.randint(jax.random.PRNGKey(11), (21, 200), 0, 10,
                              jnp.int32)
    got = ops.pathfinder(wall, strategy=strategy, tile_rows=8)
    np.testing.assert_array_equal(np.asarray(got)[0],
                                  ref.pathfinder_ref(wall))


def test_hotspot_rejects_unaligned_tile_rows():
    t = jnp.ones((24, 128), jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        ops.hotspot(t, t, tile_rows=12, strategy=Strategy.OVERLAP)


def test_lud_window_start_is_lane_aligned():
    for hi in range(32, 2048, 32):
        r0 = lud._window_start(2048, hi)
        assert r0 <= hi and (2048 - r0) % 128 == 0 and hi - r0 < 128


def test_lud_widened_window_matches_oracle():
    # n = 256: the trailing window is widened back to a lane multiple at
    # every step but the ones starting on a 128 boundary
    n = 256
    a = jax.random.normal(jax.random.PRNGKey(12), (n, n)) + n * jnp.eye(n)
    got = np.asarray(ops.lud(a, bs=32, strategy=Strategy.OVERLAP))
    np.testing.assert_allclose(got, ref.lud_ref(a), rtol=2e-4, atol=2e-4)


def test_lud_pads_to_lane_multiple():
    n = 96
    a = jax.random.normal(jax.random.PRNGKey(13), (n, n)) + n * jnp.eye(n)
    got = np.asarray(ops.lud(a, bs=32, strategy=Strategy.SYNC))
    assert got.shape == (n, n)
    np.testing.assert_allclose(got, ref.lud_ref(a), rtol=2e-4, atol=2e-4)


# --- the tuner models the kernels that run -----------------------------------

@pytest.mark.parametrize("kernel,shape", [("hotspot", (1024, 1024)),
                                          ("nw", (2048,)),
                                          ("pathfinder", (100, 100000))])
def test_tuner_row_tiles_are_sublane_aligned(kernel, shape):
    # Mosaic refuses a 32-bit row slice that is not a multiple of 8 rows
    tiles = {c.config["tile_rows"]
             for c in SearchSpace(kernel, shape).candidates()}
    assert tiles and all(t % 8 == 0 for t in tiles)


def test_tuner_vmem_counts_hotspot_halo_band():
    # 1000 columns pad to 1024 lanes; a tile_rows=8 band holds 8 + 16 rows
    cfg = dict(strategy=Strategy.OVERLAP, depth=2, wait_group=None,
               out_depth=2, tile_rows=8)
    c = SearchSpace("hotspot", (1024, 1000)).annotate(cfg)
    rings = 2 * (8 + 16) * 1024 * 4 + 2 * 8 * 1024 * 4 + 2 * 8 * 1024 * 4
    assert rings <= c.vmem_bytes < rings + 64      # + 1-element stage stubs


def test_tuner_pathfinder_candidates_run_at_any_row_count():
    # rows - 1 = 20 divides none of the tile heights; the wrapper pads
    space = SearchSpace("pathfinder", (21, 200))
    cands = space.candidates()
    assert {c.config["tile_rows"] for c in cands} == {8, 16, 32}
    assert all(space.spec.n_tiles(space.shape, c.config)
               == -(-20 // c.config["tile_rows"]) for c in cands)
    wall = jax.random.randint(jax.random.PRNGKey(14), (21, 200), 0, 10,
                              jnp.int32)
    cfg = next(c.config for c in cands if c.config["tile_rows"] == 16)
    got = ops.pathfinder(wall, **cfg)
    np.testing.assert_array_equal(np.asarray(got)[0],
                                  ref.pathfinder_ref(wall))
