#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Set-up (the
program's import, inputs and weights made from the seed, warm-up of every
shape the cell uses) is timed as ``setup_s``; then the cell's work runs
for ``--seconds`` seconds.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` a profiler trace of a few
seconds in the middle of the window gives its per-layer metrics instead.
What the window produced is then checked against a plain reference; each
number compared is printed with its limit as the last lines of standard
error.  The last line of standard output is the result, one JSON object.

It exits non-zero and prints no result when JAX finds no TPU or fewer
chips than the cell asks for, and when the program under test (``src/``)
is not in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if HERE in sys.path:
        sys.path.remove(HERE)
    sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]
    from chipbench.harness import NoDevice, run_cell, use_compile_cache
    use_compile_cache()
    try:
        line = run_cell(args.workload, args.seed, args.seconds,
                        bool(args.trace), t_start=T_START)
    except NoDevice as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
