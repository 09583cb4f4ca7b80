"""Find the highest arrival rate a serving mix's schedule sustains.

    JAX_PLATFORMS=cpu python chipbench/tools/knee_sweep.py shared-prefix \\
        0.1 0.15 0.2 0.25 0.3 [--config tiny-dense] [--requests 1536]

Arrivals are in scheduler steps (one prefill chunk or one decode step is a
step), so the schedule depends only on the mix's lengths, batch, arena and
chunk width, not on the model's widths or the chip.  This serves the mix
through the program's scheduler with a small model of the same kind on
the CPU, counting steps by the decode and chunk dispatches, and prints for
each rate how long requests waited for their first token, in steps, in the
first and the last quarter of the run, after a set-up that serves each
system prompt once, as the benchmark's own does.  Below the knee the wait stays
flat; above it the queue grows and the wait grows with it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [CHECKOUT, os.path.join(CHECKOUT, "src")]


def sweep(mix_name: str, rates, config: str, n_requests: int):
    import numpy as np
    from chipbench.drivers.serve import program_config, warm_prompts
    from chipbench.harness import HERE as BENCH, Manifest
    from repro.launch.serve import ServingLoop
    from repro.serve import Request
    import jax

    data = os.path.join(BENCH, "tests", "data")
    m = Manifest(search=(BENCH, data))
    c = m.json("configs", config)
    ref, gen = m.module("models", c["reference"]), m.module("traffic", "gen")
    params = ref.make_weights(c, jax.random.PRNGKey(0))
    out = []
    for rate in rates:
        mix = dict(m.json("traffic", mix_name), rate_per_step=rate,
                   n_requests=n_requests)
        srv = mix["server"]
        loop = ServingLoop(program_config(c), params, batch=srv["batch"],
                           max_new=mix["output_len"][1],
                           block_len=c["block_len"], max_seq=srv["max_seq"],
                           total_tokens=srv.get("total_tokens"),
                           chunk_tokens=srv.get("chunk_tokens"),
                           prefix_cache=bool(srv.get("prefix_cache")))
        loop.run([Request(uid=-1 - i, prompt=p, max_new=2) for i, p in
                  enumerate(warm_prompts(mix, gen, 1, c["vocab"]))],
                 max_steps=2)
        sched = loop.scheduler
        clock = [0]
        first = {}

        def counted(fn):
            def call(*a):
                clock[0] += 1
                return fn(*a)
            return call
        sched._decode = counted(sched._decode)
        get_chunk = sched._get_chunk
        sched._get_chunk = lambda w: counted(get_chunk(w))

        class Tokens(list):
            def __init__(self, uid):
                super().__init__()
                self.uid = uid

            def append(self, t):
                first.setdefault(self.uid, clock[0])
                list.append(self, t)

        reqs = [Request(uid=r["uid"], prompt=r["prompt"],
                        max_new=r["max_new"], arrival=r["arrival"],
                        out_tokens=Tokens(r["uid"]))
                for r in gen.requests(mix, 1, c["vocab"])]
        loop.run(reqs, max_steps=mix["output_len"][1])
        wait = np.array([first[r.uid] - r.arrival for r in reqs])
        q = len(wait) // 4
        row = {"rate_per_step": rate, "steps": clock[0],
               "wait_first_quarter": float(np.mean(wait[:q])),
               "wait_last_quarter": float(np.mean(wait[-q:])),
               "wait_p95_last_quarter": float(np.percentile(wait[-q:], 95)),
               "hit_share": sched.cache.hit_tokens / max(
                   1, sched.cache.hit_tokens + sched.cache.miss_tokens)}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mix")
    ap.add_argument("rates", type=float, nargs="+")
    ap.add_argument("--config", default="tiny-dense")
    ap.add_argument("--requests", type=int, default=1536)
    args = ap.parse_args(argv)
    sweep(args.mix, args.rates, args.config, args.requests)
    return 0


if __name__ == "__main__":
    sys.exit(main())
