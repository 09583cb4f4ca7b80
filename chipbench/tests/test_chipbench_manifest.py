"""BENCHMARK.json and the files its names find."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench.harness import CHECKOUT, HERE, NAME, UNIT, Manifest, run_cell

M = Manifest()
D = M.data
CELLS = [w["name"] for w in D["workloads"]]
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_entries_have_exactly_the_contract_keys():
    assert set(D) == KEYS["top"]
    for part in ("configs", "workloads"):
        for e in D[part]:
            assert set(e) == KEYS[part], e
    for part in ("end_to_end", "per_layer"):
        for e in D[part]:
            assert set(e) - {"workloads"} == KEYS[part], e
    assert D["command"] == ["python3", "chipbench/run.py"]
    assert D["paths"] == ["chipbench"]


def test_names_units_and_text_use_the_allowed_characters():
    names = [e["name"] for part in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in D[part]]
    assert len(names) == len(set(names))
    for n in names + [w["config"] for w in D["workloads"]] + \
            [w["traffic"] for w in D["workloads"]] + \
            [k for c in D["configs"] for k in c["reduced"]]:
        assert NAME.match(n), n
    for m in D["end_to_end"] + D["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for e in D["configs"] + D["workloads"] + D["per_layer"]:
        for k in ("why", "layer", "source"):
            if k in e:
                assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                    and "\t" not in e[k], e


def test_sources_and_bounds():
    for m in D["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in D["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert {m["name"] for m in D["end_to_end"]} == {
        "tokens_per_s", "tpot_p95_ms", "ttft_p95_ms", "setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_finds_its_files_by_name(cell):
    w = M.workload(cell)
    assert w["chips"] == 1
    config = M.json("configs", w["config"])
    traffic = M.json("traffic", w["traffic"])
    assert hasattr(M.module("drivers", config["kind"]), "run")
    if config["kind"] == "serve":
        assert hasattr(M.module("models", config["reference"]), "forward")
        assert {"server", "check"} <= set(traffic)
    else:
        for k in traffic["sequence"]:
            mod = M.module("kernels", k)
            assert k in config["kernels"] and hasattr(mod, "reference")
            assert f"{k}_rel_err" in traffic["check"]
    for m in M.per_layer(cell):
        assert hasattr(M.module("metrics", m["name"]), "read")
    entry = next(c for c in D["configs"] if c["name"] == w["config"])
    assert os.path.isfile(os.path.join(CHECKOUT, entry["file"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_metric_and_a_layer(cell):
    e2e = {m["name"] for m in M.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert M.per_layer(cell)


@pytest.mark.parametrize("metric", [m["name"] for m in D["per_layer"]])
def test_each_per_layer_metric_moves_an_end_to_end_one_its_cells_report(
        metric):
    m = next(x for x in D["per_layer"] if x["name"] == metric)
    for cell in m["workloads"]:
        assert cell in CELLS
        assert m["moves"] in {e["name"] for e in M.end_to_end(cell)}


def test_run_seconds_fits_the_check_with_24_cells():
    rs = D["run_seconds"]
    runs = 2 + 14 * 24
    assert 1 <= rs <= 51
    assert runs * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_a_cell_made_only_of_new_files_is_picked_up(tmp_path):
    """A new configuration, mix and cell in another directory, found by
    name beside the benchmark's own files, run through the harness."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "small-kernels.json").write_text(json.dumps(
        {"kind": "kernels", "kernels": {"stream": {"shape": [32, 128],
                                                   "iters": 3}}}))
    (tmp_path / "traffic" / "stream-only.json").write_text(json.dumps(
        {"sequence": ["stream"], "check": {"stream_rel_err": 0.0}}))
    bench = dict(D, workloads=D["workloads"] + [
        {"name": "small-kernels.stream", "config": "small-kernels",
         "traffic": "stream-only", "chips": 1, "why": "test"}])
    bench["end_to_end"] = [m for m in D["end_to_end"]
                           if m["name"] != "call_ms"] + [
        {"name": "call_ms", "unit": "ms", "better": "lower", "bound": 0.05,
         "source": "host_clock", "workloads": ["small-kernels.stream"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    m = Manifest(tmp_path / "BENCHMARK.json", search=(tmp_path, HERE))
    line = run_cell("small-kernels.stream", 2 ** 35 + 1, 0.3, False,
                    manifest=m, require_tpu=False)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"call_ms", "setup_s"}
    assert line["attempted"] >= 1
    assert list(line)[-1] == "checks"


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", str(2 ** 34 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_exits_non_zero_with_no_result_without_a_tpu():
    p = _run(CHECKOUT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_non_zero_without_the_program(tmp_path):
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    p = _run(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr
