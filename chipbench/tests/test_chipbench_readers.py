"""The per-layer metric readers, on hand-made readings: each returns its
share, or nothing where it finds nothing to read (never 0 for a roofline)."""
import pytest

from chipbench import counts
from chipbench.devtrace import ProgramTime, Reduction
from chipbench.harness import Manifest, Readings
from chipbench.peaks import least_seconds, peaks_for

M = Manifest()
P = peaks_for("TPU v5 lite")
QWEN = M.json("configs", "qwen2-1.5b")
KERNELS = {"hotspot": {"shape": [1024, 1024], "iters": 1},
           "pathfinder": {"shape": [100, 100000]},
           "nw": {"n": 2048, "penalty": 10}, "lud": {"n": 2048},
           "stream": {"shape": [16384, 8192], "iters": 4}}


def _red(programs, busy=0.8, window=1.0):
    return Reduction(window, busy, {k: ProgramTime(*v)
                                    for k, v in programs.items()}, [], [])


def _read(name, trace, data, config=QWEN):
    return M.module("metrics", name).read(
        Readings(config, trace, P, 10.0, data))


def test_prefix_hit_share():
    assert _read("prefix_hit_share", None, {"prefix_hit_tokens": 850,
                                            "prefix_miss_tokens": 150}) \
        == pytest.approx(85.0)
    assert _read("prefix_hit_share", None, {"prefix_hit_tokens": 0,
                                            "prefix_miss_tokens": 0}) is None


def test_prefill_share_and_idle():
    red = _red({"_decode": (10, 0.5), "chunk_step": (10, 0.2)})
    assert _read("prefill_share", red, {}) == pytest.approx(25.0)
    assert _read("prefill_share", _red({"_decode": (1, 0.5)}), {}) is None
    assert _read("idle_share.serve", red, {}) == pytest.approx(20.0)
    assert _read("idle_share.kernels", red, {}) == pytest.approx(20.0)


def test_decode_roofline_is_least_over_device_time_per_step():
    steps = [([100, 200], 20), ([101, 201], 20)]
    least = sum(least_seconds(counts.decode_step_flops(QWEN, pos),
                              counts.decode_step_bytes(QWEN, 2, b, 16), P)
                for pos, b in steps) / 2
    red = _red({"_decode": (4, 4 * 0.01)})
    got = _read("decode_roofline", red, {"decode_steps": steps})
    assert got == pytest.approx(100 * least / 0.01)
    assert 0 < got < 100
    assert _read("decode_roofline", _red({}), {"decode_steps": steps}) is None
    assert _read("decode_roofline", red, {"decode_steps": []}) is None


def test_mfu_counts_matmul_weights_per_token():
    got = _read("mfu.serve", None, {"tokens_computed": 1000})
    assert got == pytest.approx(
        100 * 1000 * 2 * counts.matmul_params(QWEN) / (10.0 * 197e12))
    assert _read("mfu.serve", None, {"tokens_computed": 0}) is None


def _kernel_data(ks):
    return {"kernel_counts": {k: M.module("kernels", k).counts(KERNELS[k])
                              for k in ks},
            "programs": {k: f"chipbench_{k}" for k in ks}}


def test_kernel_rooflines():
    rod = ("hotspot", "pathfinder", "nw", "lud")
    red = _red({f"chipbench_{k}": (5, 5e-3) for k in rod + ("stream",)})
    # 146.7 us of least time over 4 ms of device time per call
    assert _read("rodinia_roofline", red, _kernel_data(rod)) == \
        pytest.approx(100 * 146.65e-6 / 4e-3, rel=1e-3)
    # 1.311 ms over 1 ms reads over 100%: the reader does not cap it
    assert _read("stream_roofline", red, _kernel_data(("stream",))) == \
        pytest.approx(131.1, rel=1e-3)
    assert _read("stream_roofline", red, _kernel_data(rod)) is None
    assert _read("rodinia_roofline", _red({}), _kernel_data(rod)) is None
