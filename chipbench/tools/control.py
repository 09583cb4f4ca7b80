"""Read a cell's checks for the program and for the control, seed by seed.

    python chipbench/tools/control.py --workload <cell> --seconds <s> \\
        --seeds 11 12 13

For each seed this runs the cell once, as ``run.py`` does, and then the
control: the plain reference put in the program's place one precision
step below the configuration's (fp8 for a bf16 model, at every position of
the same prompts and served tokens; bf16 for the f32 kernels).  It prints
one JSON line per seed with every check's reading for the program and
for the control, beside the cell's limit.  The limits are set from these
readings: above the program's largest, below the control's smallest.
All seeds run in this one process, so set-up compiles once.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from chipbench.harness import run_cell, use_compile_cache
    use_compile_cache()
    for seed in args.seeds:
        line = run_cell(args.workload, seed, args.seconds, False,
                        control=True)
        print(json.dumps({"seed": seed, "metrics": line["metrics"],
                          "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
