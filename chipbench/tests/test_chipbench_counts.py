"""Operation and byte counts, from shapes alone, against hand arithmetic."""
import os

import pytest

from chipbench import counts
from chipbench.harness import Manifest

HERE = os.path.dirname(os.path.abspath(__file__))
M = Manifest()
QWEN = M.json("configs", "qwen2-1.5b")
TINY = Manifest(os.path.join(HERE, "data", "BENCHMARK.json"),
                search=(os.path.join(HERE, "data"),)).json(
                    "configs", "tiny-dense")


def test_qwen2_published_sizes():
    assert counts.param_count(QWEN) == 1_543_714_304
    # per layer: q and o 1536^2, k and v 1536 x 256, MLP 3 x 1536 x 8960;
    # plus the tied output projection 151936 x 1536
    layer = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960
    assert layer == 46_792_704
    assert counts.matmul_params(QWEN) == 28 * layer + 151936 * 1536
    assert counts.kv_bytes_per_token(QWEN) == 28 * 1024  # 28 KiB
    assert counts.token_flops(QWEN, 100) == (
        2 * 1_543_569_408 + 4 * 28 * 12 * 128 * 100)


def test_tiny_sizes():
    # 2 layers, d 64, 4 heads of 16 over 2 KV heads, ff 128, vocab 512
    layer = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128
    assert counts.matmul_params(TINY) == 2 * layer + 512 * 64
    assert counts.param_count(TINY) == (
        2 * (layer + (64 + 2 * 32) + 2 * 64) + 512 * 64 + 64)
    assert counts.kv_bytes_per_token(TINY) == 2 * 2 * 2 * 16 * 2
    assert counts.decode_step_flops(TINY, [0, 9]) == (
        2 * 2.0 * counts.matmul_params(TINY) + 4 * 2 * 4 * 16 * (1 + 10))


def test_decode_bytes_count_shared_blocks_once():
    bl = 16
    # two slots at positions 20 and 17 share blocks 5 and 6
    shared = [(20, [5, 6, 7, -1]), (17, [5, 6, 9, -1])]
    assert counts.attended_blocks(shared, bl) == 2
    # the same positions over private blocks read four
    private = [(20, [5, 6, 7, -1]), (17, [8, 10, 9, -1])]
    assert counts.attended_blocks(private, bl) == 4
    kv = counts.kv_bytes_per_token(QWEN)
    assert counts.decode_step_bytes(QWEN, 2, 2, bl) == (
        2 * 1_543_714_304 + kv * bl * 2 + kv * 2)
    assert counts.decode_step_bytes(QWEN, 2, 2, bl) < \
        counts.decode_step_bytes(QWEN, 2, 4, bl)


KERNEL_CASES = [
    # kernel, spec, flops, bytes
    ("stream", {"shape": [16384, 8192], "iters": 4},
     2 * 16384 * 8192 * 4, 2 * 16384 * 8192 * 4),
    ("stream", {"shape": [64, 256], "iters": 2}, 2 * 64 * 256 * 2,
     2 * 64 * 256 * 4),
    ("hotspot", {"shape": [1024, 1024], "iters": 1}, 15 * 1024 ** 2,
     3 * 1024 ** 2 * 4),
    ("hotspot", {"shape": [64, 128], "iters": 3}, 15 * 64 * 128 * 3,
     3 * 64 * 128 * 4),
    ("pathfinder", {"shape": [100, 100000]}, 3 * 100 * 100000,
     4 * (100 * 100000 + 100000)),
    ("pathfinder", {"shape": [9, 256]}, 3 * 9 * 256, 4 * (9 * 256 + 256)),
    ("nw", {"n": 2048, "penalty": 10}, 5 * 2048 ** 2,
     4 * (2048 ** 2 + 2049 ** 2)),
    ("nw", {"n": 64, "penalty": 10}, 5 * 64 ** 2, 4 * (64 ** 2 + 65 ** 2)),
    ("lud", {"n": 2048}, 2 * 2048 ** 3 / 3, 2 * 2048 ** 2 * 4),
    ("lud", {"n": 64}, 2 * 64 ** 3 / 3, 2 * 64 ** 2 * 4),
]


@pytest.mark.parametrize("kernel,spec,flops,nbytes", KERNEL_CASES)
def test_kernel_counts(kernel, spec, flops, nbytes):
    assert M.module("kernels", kernel).counts(spec) == (
        pytest.approx(flops), pytest.approx(nbytes))


#: the sizes Rodinia's own run scripts give each kernel, and the stream
#: cell's configuration
RODINIA = {"hotspot": {"shape": [1024, 1024], "iters": 1},
           "pathfinder": {"shape": [100, 100000]},
           "nw": {"n": 2048, "penalty": 10}, "lud": {"n": 2048}}
SIZES = dict(RODINIA, **M.json("configs", "paper-kernels")["kernels"])

TILES = {"stream": dict(tile_rows=256, n_tiles=8),
         "hotspot": dict(tile_rows=64), "pathfinder": dict(tile_rows=16),
         "nw": dict(tile_rows=16), "lud": dict(bs=64)}


@pytest.mark.parametrize("kernel", sorted(TILES))
def test_kernel_counts_ignore_the_tile_config(kernel):
    from repro.kernels import ops
    spec = SIZES[kernel]
    mod = M.module("kernels", kernel)
    before = mod.counts(spec)
    try:
        ops.set_default_config(kernel, **TILES[kernel])
        assert mod.counts(spec) == before
    finally:
        ops.reset_default_configs()


def test_rodinia_least_times_sum_to_about_147_us():
    from chipbench.peaks import least_seconds, peaks_for
    p = peaks_for("TPU v5 lite")
    least = {k: least_seconds(*M.module("kernels", k).counts(SIZES[k]), p)
             for k in RODINIA}
    # every one is bound by bytes at these sizes: 12.6 + 40.4 + 33.6 +
    # 33.6 MB at 819 GB/s
    assert sum(least.values()) == pytest.approx(146.7e-6, rel=1e-3)
    # the stream cell: 2 GiB read and written, 4.29 GB at 819 GB/s
    assert least_seconds(*M.module("kernels", "stream").counts(
        SIZES["stream"]), p) == pytest.approx(5.244e-3, rel=1e-3)
