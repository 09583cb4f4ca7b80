"""The control: the plain reference put in the program's place one
precision step below the configuration's (fp8 for the bf16 model, bf16
for the f32 kernels), at sizes a test run holds.  It must read above the
cell's limit while the program reads below it."""
import os

import pytest

from chipbench.harness import HERE, Manifest, run_cell

DATA = os.path.join(os.path.dirname(__file__), "data")
M = Manifest(os.path.join(DATA, "BENCHMARK.json"), search=(DATA, HERE))


@pytest.mark.parametrize("cell", ["tiny-dense.batch", "tiny-dense.prefix"])
def test_serving_control_fails_the_gap_limit(cell):
    line = run_cell(cell, 2 ** 37 + 5, 1.0, False, manifest=M,
                    require_tpu=False, control=True)
    got, ctl = line["checks"]["max_gap"], line["checks"]["control_max_gap"]
    assert got["value"] <= got["limit"] < ctl["value"]


def test_kernel_control_fails_a_limit():
    line = run_cell("tiny-kernels.all", 2 ** 37 + 6, 0.5, False, manifest=M,
                    require_tpu=False, control=True)
    checks = line["checks"]
    programs = {k: v for k, v in checks.items() if not k.startswith("control")}
    assert all(v["value"] <= v["limit"] for v in programs.values())
    failed = [k for k, v in checks.items()
              if k.startswith("control") and v["value"] > v["limit"]]
    assert {"control_hotspot_rel_err", "control_nw_rel_err",
            "control_lud_rel_err", "control_stream_rel_err"} <= set(failed)
