"""Roofline engine tests: loop-aware HLO cost analysis (the reason this
module exists: XLA's cost_analysis counts a while body ONCE), collective
parsing, and report arithmetic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import hardware, roofline
from repro.core.hlo_cost import analyze_hlo, cost_with_loops


def test_scan_flops_are_trip_scaled():
    def f_scan(w, x):
        def body(x, _):
            return jnp.tanh(x @ w), None
        y, _ = jax.lax.scan(body, x, None, length=8)
        return y.sum()

    w = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
    compiled = jax.jit(f_scan).lower(w, x).compile()
    ours = cost_with_loops(compiled)
    analytic = 2 * 8 * 32 * 128 * 128
    assert abs(ours.flops - analytic) / analytic < 0.05
    # XLA's own analysis undercounts by ~the trip count — the motivating bug
    xla = compiled.cost_analysis().get("flops", 0)
    assert xla < analytic / 4


def test_nonscan_flops_match_xla():
    def g(a, b):
        return jnp.tanh(a @ b).sum()
    s = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = jax.jit(g).lower(s, s).compile()
    ours = cost_with_loops(compiled)
    xla = compiled.cost_analysis().get("flops", 0)
    assert abs(ours.flops - xla) / xla < 0.05


def test_loop_invariant_weights_counted_once():
    """A weight reused across scan iterations streams to VMEM once."""
    def f(w, xs):
        def body(_, x):
            return None, jnp.tanh(x @ w)
        _, ys = jax.lax.scan(body, None, xs)
        return ys.sum()

    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)      # 256 KiB, resident
    xs = jax.ShapeDtypeStruct((64, 8, 256), jnp.float32)
    c = cost_with_loops(jax.jit(f).lower(w, xs).compile())
    w_bytes = 256 * 256 * 4
    # if charged per trip the weight alone would be 64 * 256KiB = 16 MiB
    assert c.bytes_fused < 40 * w_bytes


def test_collective_parse_ring_bytes():
    hlo = """
HloModule test

ENTRY %main (a: f32[128,64]) -> f32[128,64] {
  %a = f32[128,64]{1,0} parameter(0)
  %ar = f32[128,64]{1,0} all-reduce(%a), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = f32[512,64]{1,0} all-gather(%ar), replica_groups={{0,1,2,3}}, dimensions={0}
  ROOT %out = f32[128,64]{1,0} slice(%ag), slice={[0:128], [0:64]}
}
"""
    ops = roofline.parse_collectives(hlo)
    kinds = {o.kind for o in ops}
    assert kinds == {"all-reduce", "all-gather"}
    ar = next(o for o in ops if o.kind == "all-reduce")
    n_bytes = 128 * 64 * 4
    assert ar.wire_bytes == pytest.approx(2 * n_bytes * 3 / 4)
    ag = next(o for o in ops if o.kind == "all-gather")
    assert ag.wire_bytes == pytest.approx(n_bytes * 3)


def test_collectives_inside_loops_scaled():
    hlo = """
HloModule test

%body (arg: (s32[], f32[64,64])) -> (s32[], f32[64,64]) {
  %arg = (s32[], f32[64,64]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[64,64]{1,0} get-tuple-element(%arg), index=1
  %ar = f32[64,64]{1,0} all-reduce(%x), replica_groups={{0,1}}, to_apply=%add
  %one = s32[] constant(1)
  %i2 = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[64,64]) tuple(%i2, %ar)
}

%cond (arg2: (s32[], f32[64,64])) -> pred[] {
  %arg2 = (s32[], f32[64,64]) parameter(0)
  %j = s32[] get-tuple-element(%arg2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%j, %n), direction=LT
}

ENTRY %main (x0: f32[64,64]) -> f32[64,64] {
  %x0 = f32[64,64]{1,0} parameter(0)
  %z = s32[] constant(0)
  %init = (s32[], f32[64,64]) tuple(%z, %x0)
  %w = (s32[], f32[64,64]) while(%init), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %r = f32[64,64]{1,0} get-tuple-element(%w), index=1
}
"""
    c = analyze_hlo(hlo)
    assert c.collective_counts.get("all-reduce", 0) == 10
    per = 2 * (64 * 64 * 4) * (1 / 2)
    assert c.wire_bytes == pytest.approx(10 * per)


def test_report_terms_and_bottleneck():
    rep = roofline.RooflineReport(
        arch="a", shape="s", mesh="m", n_chips=256,
        hlo_flops=hardware.PEAK_FLOPS,          # 1 s of compute
        hlo_bytes=hardware.HBM_BW / 2,          # 0.5 s of memory
        collective_wire_bytes=hardware.ICI_BW * 2,  # 2 s of wire
        model_flops=hardware.PEAK_FLOPS / 2)
    assert rep.t_compute == pytest.approx(1.0)
    assert rep.t_memory == pytest.approx(0.5)
    assert rep.t_collective == pytest.approx(2.0)
    assert rep.bottleneck == "collective"
    assert rep.t_bound == pytest.approx(2.0)
    assert rep.useful_flops_ratio == pytest.approx(0.5)
    assert rep.roofline_fraction == pytest.approx(0.25)


def test_dtype_bytes_table():
    assert roofline.shape_bytes("f32", "8,4") == 128
    assert roofline.shape_bytes("bf16", "8,4") == 64
    assert roofline.shape_bytes("pred", "10") == 10
    assert roofline.shape_bytes("f32", "") == 4   # scalar
