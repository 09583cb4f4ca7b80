"""repro.obs subsystem tests: span tracing (nesting, retroactive record,
disabled no-op, JSONL/Chrome export), labeled metrics, the noise-aware
regression gate, and the obs CLI's exit-code contract."""
import json
import time

import pytest

from repro.bench.results import BenchReport, BenchResult
from repro.obs import compare as cmp_mod
from repro.obs import metrics as metrics_mod
from repro.obs.cli import main as obs_cli_main
from repro.obs.compare import (CompareResult, cell_noise_us, compare_reports,
                               format_compare)
from repro.obs.metrics import Registry, quantile
from repro.obs.trace import Span, Tracer, chrome_trace, load_jsonl


# --- tracing ----------------------------------------------------------------

def test_span_nesting_and_attrs():
    t = Tracer(enabled=True)
    with t.span("outer", kind="scenario") as outer:
        with t.span("inner") as inner:
            assert inner.parent_id == outer.span_id
        outer.attrs["us_median"] = 42.0     # mutable until export
    spans = t.spans()
    assert [s.name for s in spans] == ["inner", "outer"]  # close order
    assert all(s.trace_id == t.trace_id for s in spans)
    got_outer = next(s for s in spans if s.name == "outer")
    assert got_outer.attrs == {"kind": "scenario", "us_median": 42.0}
    assert got_outer.parent_id is None
    assert all(s.dur_us >= 0 for s in spans)


def test_disabled_tracer_records_nothing():
    t = Tracer()                            # disabled by default
    with t.span("nope", x=1) as sp:
        assert sp is None
    assert t.record("nope", 0.0, 1.0) is None
    assert t.spans() == []
    # the disabled span() must return one shared object, not allocate
    assert t.span("a") is t.span("b")


def test_record_is_retroactive_and_nests():
    t = Tracer(enabled=True)
    t0 = time.perf_counter()
    t1 = t0 + 0.001
    with t.span("scenario") as outer:
        sp = t.record("timed", t0, t1, trial=0, outlier=False)
    assert sp.parent_id == outer.span_id
    assert sp.dur_us == pytest.approx(1000.0)
    assert sp.attrs == {"trial": 0, "outlier": False}


def test_span_exception_annotates_and_closes():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        with t.span("boom"):
            raise ValueError("x")
    (sp,) = t.spans()
    assert sp.attrs["error"] == "ValueError"
    assert sp.t1_us is not None


def test_jsonl_round_trip(tmp_path):
    t = Tracer(enabled=True)
    with t.span("a", n=3):
        t.record("b", 1.0, 2.0)
    path = str(tmp_path / "t.jsonl")
    assert t.save_jsonl(path) == 2
    got = load_jsonl(path)
    by_name = {s.name: s for s in got}
    assert by_name["b"].parent_id == by_name["a"].span_id
    assert by_name["a"].attrs == {"n": 3}
    assert by_name["b"].t0_us == 1e6 and by_name["b"].t1_us == 2e6


def test_chrome_trace_events():
    t = Tracer(enabled=True)
    with t.span("outer"):
        t.record("early", 0.5, 0.6)        # earlier ts than outer
    doc = chrome_trace(t.spans())
    ev = doc["traceEvents"]
    assert [e["ts"] for e in ev] == sorted(e["ts"] for e in ev)
    for e in ev:
        assert e["ph"] == "X" and e["dur"] >= 0
        assert {"name", "ts", "pid", "tid", "args"} <= set(e)
        assert "span_id" in e["args"]
    assert doc["displayTimeUnit"] == "ms"
    # open spans are dropped, not exported half-finished
    open_span = Span(name="open", t0_us=0.0)
    assert chrome_trace([open_span])["traceEvents"] == []


def test_tracer_clear_resets_trace_id():
    t = Tracer(enabled=True)
    with t.span("a"):
        pass
    old = t.trace_id
    t.clear()
    assert t.spans() == [] and t.trace_id != old


def test_spans_record_while_a_profiler_session_runs(tmp_path):
    """A tracer nobody enabled records while a JAX profiler session is
    active, nests as usual, and stops recording when the session ends."""
    import jax
    t = Tracer()
    with t.span("before") as sp:
        assert sp is None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with t.span("serve.step", queued=3) as step:
            with t.span("serve.readback", n_tokens=2) as rb:
                assert rb.parent_id == step.span_id
    finally:
        jax.profiler.stop_trace()
    with t.span("after") as sp:
        assert sp is None
    assert t.span("a") is t.span("b")
    spans = t.spans()
    assert [s.name for s in spans] == ["serve.readback", "serve.step"]
    assert spans[0].attrs == {"n_tokens": 2}
    assert spans[1].attrs == {"queued": 3}
    assert all(s.dur_us >= 0 for s in spans)


def test_span_open_across_start_or_stop_trace_is_dropped(tmp_path):
    import jax
    t = Tracer()
    with t.span("across_start"):
        jax.profiler.start_trace(str(tmp_path))
        with t.span("inside_1"):
            pass
    try:
        with t.span("across_stop"):
            with t.span("inside_2"):
                pass
            jax.profiler.stop_trace()
    finally:
        if jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()
    assert [s.name for s in t.spans()] == ["inside_1", "inside_2"]


def test_profiled_spans_land_in_the_host_plane_by_bare_name(tmp_path):
    """Each span is a TraceAnnotation under its bare name: attributes stay
    on the in-memory span and never split one name into many."""
    import jax
    t = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for uid in range(3):
            with t.span("serve.step", queued=uid):
                with t.span("serve.admit", uid=uid, waited_ms=1.5):
                    pass
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    names = [e.name for p in data.planes if p.name == "/host:CPU"
             for ln in p.lines for e in ln.events
             if e.name.startswith("serve.")]
    assert sorted(names) == ["serve.admit"] * 3 + ["serve.step"] * 3
    assert [s.attrs["uid"] for s in t.spans()
            if s.name == "serve.admit"] == [0, 1, 2]


# --- metrics ----------------------------------------------------------------

def test_counter_gauge_basics():
    r = Registry()
    c = r.counter("reqs")
    c.inc()
    c.inc(2)
    assert c.value == 3
    g = r.gauge("depth")
    g.set(5)
    g.inc(-2)
    assert g.value == 3
    # same (name, labels) -> same instance; different labels -> distinct
    assert r.counter("reqs") is c
    assert r.counter("reqs", arch="a") is not c


def test_histogram_quantiles_and_ring():
    r = Registry()
    h = r.histogram("lat")
    for v in range(1, 101):
        h.observe(float(v))
    snap = h.snapshot()
    assert snap["count"] == 100 and snap["sum"] == 5050.0
    assert snap["min"] == 1.0 and snap["max"] == 100.0
    assert snap["p50"] == pytest.approx(50.5)
    assert snap["p99"] == pytest.approx(99.01)
    # ring: quantiles describe the recent window, totals stay exact
    small = metrics_mod.Histogram("w", (), max_samples=4)
    for v in [1.0, 2.0, 3.0, 4.0, 100.0, 100.0]:
        small.observe(v)
    snap = small.snapshot()
    assert snap["count"] == 6 and snap["sum"] == 210.0
    assert snap["p50"] >= 3.5                  # 1.0/2.0 were overwritten


def test_registry_snapshot_and_save(tmp_path):
    r = Registry()
    r.counter("b").inc()
    r.histogram("a", arch="x").observe(1.0)
    rows = r.snapshot()
    assert [row["name"] for row in rows] == ["a", "b"]   # sorted
    assert rows[0]["labels"] == {"arch": "x"}
    path = str(tmp_path / "m.json")
    r.save(path)
    doc = json.load(open(path))
    assert doc["kind"] == "obs-metrics" and len(doc["rows"]) == 2


def test_quantile_edges():
    assert quantile([], 0.5) == 0.0
    assert quantile([7.0], 0.99) == 7.0
    assert quantile([1.0, 3.0], 0.5) == 2.0
    assert quantile([1.0, 2.0, 3.0, 4.0], 1.0) == 4.0


# --- regression gate --------------------------------------------------------

def _row(scenario, us_median, times=None, chip="TPUv5e", kind="measured",
         **kw):
    metrics = {"us_median": us_median}
    if times is not None:
        metrics["times_us"] = times
        metrics["us_std"] = 0.0
    base = dict(scenario=scenario, kernel="stream", shape=[256, 256],
                dtype="float32", strategy="overlap", chip=chip,
                metrics=metrics, kind=kind, interpret=True)
    base.update(kw)
    return BenchResult(**base)


def _report(*rows):
    r = BenchReport(jax_version="0", backend="cpu")
    r.extend(rows)
    return r


TIGHT = [100.0, 100.5, 101.0, 101.5, 102.0]     # IQR = 1.0


def test_identical_reports_all_pass():
    rep = _report(_row("a", 101.0, TIGHT), _row("b", 50.0, [50.0] * 5))
    res = compare_reports(rep, rep)
    assert res.n_regressions == 0
    assert res.counts() == {"pass": 2, "regress": 0, "improve": 0,
                            "new": 0, "missing": 0}


def test_regress_and_improve_beyond_noise_band():
    base = _report(_row("a", 101.0, TIGHT))
    slow = _report(_row("a", 130.0, [130.0] * 5))   # >> 3*IQR and >5%
    fast = _report(_row("a", 80.0, [80.0] * 5))
    assert compare_reports(base, slow).verdicts[0].verdict == "regress"
    assert compare_reports(base, slow).n_regressions == 1
    assert compare_reports(base, fast).verdicts[0].verdict == "improve"


def test_band_scales_with_baseline_noise():
    """The same absolute delta passes on a noisy cell and flags on a
    quiet one — the whole point of a noise-aware gate."""
    noisy = [80.0, 95.0, 105.0, 120.0, 130.0]       # IQR = 25
    new = _report(_row("a", 130.0, [130.0] * 5))
    assert compare_reports(_report(_row("a", 101.0, noisy)),
                           new).verdicts[0].verdict == "pass"
    assert compare_reports(_report(_row("a", 101.0, TIGHT)),
                           new).verdicts[0].verdict == "regress"


def test_candidate_noise_cannot_widen_the_gate():
    """A regression that also inflates its own variance must still flag:
    the band comes from the BASELINE's spread only."""
    base = _report(_row("a", 101.0, TIGHT))
    slow_noisy = _report(_row("a", 1010.0, [t * 10 for t in TIGHT]))
    assert compare_reports(base, slow_noisy).verdicts[0].verdict == "regress"


def test_rel_floor_absorbs_zero_iqr_jitter():
    base = _report(_row("a", 100.0, [100.0] * 5))    # zero spread
    within = _report(_row("a", 104.0, [104.0] * 5))  # +4% < 5% floor
    beyond = _report(_row("a", 106.0, [106.0] * 5))
    assert compare_reports(base, within).verdicts[0].verdict == "pass"
    assert compare_reports(base, beyond).verdicts[0].verdict == "regress"


def test_normalize_absorbs_uniform_host_speed():
    """A uniformly 2x slower host is machine lottery, not a regression —
    but a cell that moved relative to its own sweep still flags."""
    base = _report(_row("a", 100.0, [100.0] * 5),
                   _row("b", 200.0, [200.0] * 5),
                   _row("c", 300.0, [300.0] * 5))
    uniform = _report(_row("a", 200.0, [200.0] * 5),
                      _row("b", 400.0, [400.0] * 5),
                      _row("c", 600.0, [600.0] * 5))
    res = compare_reports(base, uniform, normalize=True)
    assert res.host_scale == pytest.approx(2.0)
    assert res.n_regressions == 0
    # same host scale, but cell "c" regressed 3x on top of it
    mixed = _report(_row("a", 200.0, [200.0] * 5),
                    _row("b", 400.0, [400.0] * 5),
                    _row("c", 1800.0, [1800.0] * 5))
    res = compare_reports(base, mixed, normalize=True)
    bad = [v for v in res.verdicts if v.verdict == "regress"]
    assert [v.scenario for v in bad] == ["c"]
    # without normalization all three cells flag
    assert compare_reports(base, mixed).n_regressions == 3


def test_missing_new_and_model_rows():
    base = _report(_row("a", 100.0, TIGHT), _row("gone", 50.0, [50.0] * 5),
                   _row("proj", 1.0, kind="model"))
    new = _report(_row("a", 100.5, TIGHT), _row("added", 70.0, [70.0] * 5),
                  _row("proj", 99.0, kind="model"))
    res = compare_reports(base, new)
    got = {v.scenario: v.verdict for v in res.verdicts}
    # model rows are roofline predictions, never gated
    assert got == {"a": "pass", "gone": "missing", "added": "new"}
    assert res.n_regressions == 0               # missing/new do not gate


def test_cell_noise_falls_back_to_std():
    assert cell_noise_us({"times_us": TIGHT}) == pytest.approx(1.0)
    # < 4 samples or no samples: derived from the std instead
    assert cell_noise_us({"times_us": [1.0, 2.0], "us_std": 2.0}) == \
        pytest.approx(cmp_mod._STD_TO_IQR * 2.0)
    assert cell_noise_us({"us_std": 0.0}) == 0.0
    assert cell_noise_us({}) == 0.0


def test_compare_result_round_trip(tmp_path):
    res = compare_reports(_report(_row("a", 101.0, TIGHT)),
                          _report(_row("a", 130.0, [130.0] * 5)))
    path = str(tmp_path / "CMP.json")
    res.save(path)
    got = CompareResult.load(path)
    assert got.counts() == res.counts()
    assert got.verdicts[0].verdict == "regress"
    assert got.verdicts[0].delta_pct == pytest.approx(
        res.verdicts[0].delta_pct)
    with pytest.raises(ValueError):
        CompareResult.from_dict({"kind": "not-a-compare"})


def test_format_compare_mentions_gate_and_regressions():
    res = compare_reports(_report(_row("a", 101.0, TIGHT)),
                          _report(_row("a", 130.0, [130.0] * 5)))
    text = format_compare(res, base_path="B.json", new_path="N.json")
    assert "GATE: REGRESSED" in text and "regress" in text
    ok = compare_reports(_report(_row("a", 101.0, TIGHT)),
                         _report(_row("a", 101.0, TIGHT)))
    assert "GATE: ok" in format_compare(ok)


# --- CLI --------------------------------------------------------------------

def _save_report(tmp_path, name, *rows):
    path = str(tmp_path / name)
    _report(*rows).save(path)
    return path


def test_cli_compare_exit_codes(tmp_path, capsys):
    base = _save_report(tmp_path, "B.json", _row("a", 101.0, TIGHT))
    same = _save_report(tmp_path, "S.json", _row("a", 101.2, TIGHT))
    slow = _save_report(tmp_path, "R.json", _row("a", 130.0, [130.0] * 5))
    assert obs_cli_main(["compare", base, same]) == 0
    assert "GATE: ok" in capsys.readouterr().out
    out_json = str(tmp_path / "CMP.json")
    assert obs_cli_main(["compare", base, slow, "--json", out_json]) == 1
    assert "GATE: REGRESSED" in capsys.readouterr().out
    assert json.load(open(out_json))["counts"]["regress"] == 1
    # gate knobs pass through: a huge rel-floor waives the regression
    assert obs_cli_main(["compare", base, slow, "--rel-floor", "0.5"]) == 0
    capsys.readouterr()


def test_cli_summary_and_export_trace(tmp_path, capsys):
    t = Tracer(enabled=True)
    with t.span("scenario:x", kernel="stream"):
        t.record("timed", 1.0, 1.001, trial=0)
    jsonl = str(tmp_path / "t.jsonl")
    t.save_jsonl(jsonl)
    r = Registry()
    r.histogram("serve.ttft_ms").observe(12.0)
    mpath = str(tmp_path / "m.json")
    r.save(mpath)

    assert obs_cli_main(["summary", "--trace", jsonl,
                         "--metrics", mpath]) == 0
    out = capsys.readouterr().out
    assert "scenario:x" in out and "serve.ttft_ms" in out

    chrome = str(tmp_path / "t.chrome.json")
    assert obs_cli_main(["export-trace", jsonl, chrome]) == 0
    capsys.readouterr()
    doc = json.load(open(chrome))
    assert len(doc["traceEvents"]) == 2
    assert {e["name"] for e in doc["traceEvents"]} == {"scenario:x", "timed"}


def test_cli_summary_requires_an_input():
    with pytest.raises(SystemExit):
        obs_cli_main(["summary"])


def test_obs_package_imports_stay_acyclic():
    """bench.timing imports obs.trace, so importing the obs package alone
    must never pull in repro.bench (the compare module is lazy)."""
    import subprocess
    import sys
    code = ("import sys; import repro.obs; "
            "bad = [m for m in sys.modules if m.startswith('repro.bench')]; "
            "sys.exit(1 if bad else 0)")
    proc = subprocess.run([sys.executable, "-c", code],
                          env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"},
                          cwd=str(__import__("pathlib").Path(
                              __file__).resolve().parent.parent))
    assert proc.returncode == 0, \
        "importing repro.obs eagerly imported repro.bench.*"
