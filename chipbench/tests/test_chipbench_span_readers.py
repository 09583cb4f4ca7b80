"""The readers of the program's serving spans (``host_share.serve``,
``first_token_backlog``), on hand-made spans and through the process's
tracer: each returns its value, or nothing where no ``serve.step`` was
recorded."""
import io

import pytest

from chipbench.devtrace import Reduction
from chipbench.harness import Manifest, Readings
from chipbench.peaks import peaks_for
from repro.obs.trace import Span, tracer

M = Manifest()
HOST = M.module("metrics", "host_share.serve")
BACKLOG = M.module("metrics", "first_token_backlog")


def _span(name, t0_ms, t1_ms, parent=None, **attrs):
    return Span(name=name, t0_us=t0_ms * 1e3, t1_us=t1_ms * 1e3, attrs=attrs,
                parent_id=parent.span_id if parent else None)


def _iteration(t0, queued, prefilling, admit_wait=None):
    """One 100 ms step: 20 ms dispatch, 50 ms waiting on the device,
    25 ms readback, 5 ms in no child."""
    st = _span("serve.step", t0, t0 + 100, active=4, queued=queued,
               prefilling=prefilling)
    kids = [_span("serve.decode_step", t0, t0 + 20, st, active=4),
            _span("serve.device_wait", t0 + 20, t0 + 70, st, on="decode"),
            _span("serve.readback", t0 + 70, t0 + 95, st, n_tokens=4,
                  finished=0)]
    if admit_wait is not None:
        kids.append(_span("serve.admit", t0 + 95, t0 + 95, st, uid=1,
                          prompt_len=8, matched=0, waited_ms=admit_wait))
    return kids + [st]


def test_host_share_is_steps_less_their_device_waits():
    spans = _iteration(0, 0, 0) + _iteration(100, 0, 0)
    out = io.StringIO()
    assert HOST.host_share(spans, idle_pct=41.0, out=out) == \
        pytest.approx(50.0)
    said = out.getvalue()
    for part in ("serve.device_wait 50.00%", "serve.readback 25.00%",
                 "serve.decode_step 20.00%", "unspanned 5.00%",
                 "device idle 41.00%", "2 steps"):
        assert part in said


def test_host_share_ignores_spans_outside_recorded_steps():
    spans = _iteration(0, 0, 0)
    orphan = _span("serve.device_wait", 200, 900, on="decode")
    assert HOST.host_share(spans + [orphan], out=io.StringIO()) == \
        pytest.approx(50.0)


def test_backlog_is_time_weighted_queued_plus_prefilling():
    spans = (_iteration(0, 3, 1, admit_wait=10.0)
             + _iteration(100, 0, 2, admit_wait=30.0))
    # a step of 300 ms holding 1 request weighs three times as much
    long = _span("serve.step", 200, 500, active=4, queued=1, prefilling=0)
    out = io.StringIO()
    got = BACKLOG.first_token_backlog(spans + [long], out=out)
    assert got == pytest.approx((4 * 100 + 2 * 100 + 1 * 300) / 500)
    said = out.getvalue()
    assert "queued 1.200" in said and "prefilling 0.600" in said
    assert "20.0 ms over 2" in said


@pytest.mark.parametrize("reader", ["host_share", "first_token_backlog"])
def test_no_steps_reads_nothing(reader):
    fn = getattr(HOST if reader == "host_share" else BACKLOG, reader)
    assert fn([], out=io.StringIO()) is None
    only_children = [s for s in _iteration(0, 1, 1) if s.name != "serve.step"]
    assert fn(only_children, out=io.StringIO()) is None


def test_readers_take_the_process_tracer_spans():
    red = Reduction(1.0, 0.6, {}, [], [])
    r = Readings({}, red, peaks_for("TPU v5 lite"), 10.0, {})
    tr = tracer()
    tr.clear()
    assert HOST.read(r) is None and BACKLOG.read(r) is None
    tr.enable()
    try:
        with tr.span("serve.step", active=2, queued=5, prefilling=1):
            tr.record("serve.device_wait", 0.0, 0.0, on="decode")
    finally:
        tr.disable()
    try:
        assert 0 < HOST.read(r) <= 100
        assert BACKLOG.read(r) == pytest.approx(6.0)
    finally:
        tr.clear()
