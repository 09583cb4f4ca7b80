"""Record the small profiler trace that the trace-reduction tests read.

    python chipbench/tools/record_trace.py OUT.xplane.pb

Runs two named jitted programs on the default device under
``jax.profiler``: ``chipbench_probe`` (an XLA matmul chain) and
``chipbench_stream`` (the program's stream kernel at a small size), a few
calls each with host work between them, and copies the trace file to OUT.
It prints every plane and line of the trace with its event count and the
first event names, so the naming the reduction relies on can be read off.
"""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.kernels import ops

    def chipbench_probe(x):
        return jnp.tanh(x @ x) @ x

    def chipbench_stream(x):
        return ops.stream(x, iters=4)

    probe, stream = jax.jit(chipbench_probe), jax.jit(chipbench_stream)
    x = jax.random.normal(jax.random.PRNGKey(0), (1024, 1024), jnp.float32)
    probe(x).block_until_ready()
    stream(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    try:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            probe(x).block_until_ready()
            time.sleep(0.002)
            stream(x).block_until_ready()
            time.sleep(0.002)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                            recursive=True)
        shutil.copyfile(path, out)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    data = ProfileData.from_file(out)
    summary = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"line": line.name, "n": len(evs),
                          "first": sorted({e.name for e in evs})[:12]})
        summary.append({"plane": plane.name, "lines": lines})
    print(json.dumps(summary, indent=1))
    dev = jax.devices()[0]
    print(json.dumps({"platform": dev.platform, "kind": dev.device_kind,
                      "bytes": os.path.getsize(out),
                      "memory_stats": sorted((dev.memory_stats() or {}))}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
