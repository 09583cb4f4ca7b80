"""The one traffic generator: a mix file of parameters -> requests.

A serving mix (``chipbench/traffic/<name>.json``) gives:

- ``n_requests``: how many requests the list holds (more than a window
  can finish, so the queue never drains inside it);
- ``block``: requests are made in blocks of this many, and every block
  holds the same requests' shapes (prompt length, output length and
  system-prompt pick) and the same arrival gaps, each block in an order
  of its own.  The shapes, gaps and orders are the same for every seed:
  the seed draws only the token ids of the prompts and of the system
  prompts.  So every seed, and every prefix of the list, carries the same
  work, which a window of a few dozen requests needs for its tails to
  repeat from seed to seed;
- ``prompt_len`` and ``output_len``: closed ranges ``[lo, hi]``, spread
  evenly over each block (the uniform distribution's quantiles); with
  ``prompt_len_step``, prompt lengths lie on the grid ``lo + k * step``;
- ``arrival``: ``all_at_start``, or ``poisson`` at ``rate_per_step``
  requests per scheduler step (one prefill chunk or one decode step is a
  step);
- optionally ``system_prompts``: ``count`` prompts of ``len`` tokens, each
  request prefixed by one of them picked with Zipf(``zipf_s``)
  popularity; ``prompt_len`` is then the length of the request's own tail.

The arrival arithmetic is that of the program's ``serve/traces.py``:
exponential gaps of mean ``1 / rate`` for poisson.  Here the gaps are the
distribution's quantiles in a fixed order rather than independent draws,
for the reason given under ``block``.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

ARRIVALS = ("all_at_start", "poisson")


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _even_ints(lo: int, hi: int, n: int, step: int = 1) -> np.ndarray:
    """``n`` integers spread evenly over ``lo, lo + step, ..., hi``."""
    k = (hi - lo) // step + 1
    return lo + step * np.floor(_quantiles(n) * k).astype(np.int64)


def prompt_lengths(mix: Dict) -> List[int]:
    """Every prompt length the mix can produce."""
    lo, hi = mix["prompt_len"]
    step = int(mix.get("prompt_len_step", 1))
    extra = int(mix["system_prompts"]["len"]) if mix.get("system_prompts") \
        else 0
    return [extra + p for p in range(lo, hi + 1, step)]


def zipf_counts(n: int, count: int, s: float) -> np.ndarray:
    """How many of ``n`` picks go to each of ``count`` items under
    Zipf(s) popularity (item g has weight 1/(g+1)^s), rounded so the
    counts sum to ``n`` (largest remainders first)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    rest = n - out.sum()
    out[np.argsort(-(exact - out), kind="stable")[:rest]] += 1
    return out


def _gaps(kind: str, n: int, rate: float) -> np.ndarray:
    if kind == "all_at_start":
        return np.zeros(n)
    if kind != "poisson":
        raise ValueError(f"unknown arrival {kind!r}; want one of {ARRIVALS}")
    if rate <= 0:
        raise ValueError("rate_per_step must be > 0 for poisson")
    return -np.log1p(-_quantiles(n)) / rate


def system_prompts(mix: Dict, seed: int, vocab: int) -> List[np.ndarray]:
    """The mix's system prompts for ``seed`` (none without
    ``system_prompts``)."""
    sysp = mix.get("system_prompts")
    if not sysp:
        return []
    rng = np.random.default_rng([seed, 7])
    return [rng.integers(0, vocab, int(sysp["len"]), dtype=np.int32)
            for _ in range(int(sysp["count"]))]


def requests(mix: Dict, seed: int, vocab: int) -> List[Dict]:
    """The mix's requests for ``seed``: dicts with ``uid``, ``prompt``
    (int32 token ids), ``max_new``, ``arrival`` (in scheduler steps) and
    ``group`` (system prompt index, or -1)."""
    n, m = int(mix["n_requests"]), int(mix["block"])
    if n % m:
        raise ValueError(f"n_requests {n} is not a multiple of block {m}")
    kind = mix["arrival"]
    rate = float(mix.get("rate_per_step", 0.0))
    rng = np.random.default_rng(seed)
    sysp = mix.get("system_prompts")
    prefixes = system_prompts(mix, seed, vocab)
    if sysp:
        groups_block = np.repeat(np.arange(int(sysp["count"])), zipf_counts(
            m, int(sysp["count"]), float(sysp["zipf_s"])))
    # shapes, gaps and orders: the same for every seed
    fixed = np.random.default_rng(0)
    plens = _even_ints(*mix["prompt_len"], m,
                       int(mix.get("prompt_len_step", 1)))
    outs = fixed.permutation(_even_ints(*mix["output_len"], m))
    if sysp:
        groups_block = fixed.permutation(groups_block)
    gaps_block = _gaps(kind, m, rate)
    out: List[Dict] = []
    clock = 0.0
    for b in range(n // m):
        order = fixed.permutation(m)
        gaps = fixed.permutation(gaps_block)
        for i, j in enumerate(order):
            clock += gaps[i]
            tail = rng.integers(0, vocab, int(plens[j]), dtype=np.int32)
            g = int(groups_block[j]) if sysp else -1
            prompt = np.concatenate([prefixes[g], tail]) if g >= 0 else tail
            out.append({"uid": b * m + i, "prompt": prompt,
                        "max_new": int(outs[j]), "arrival": float(clock),
                        "group": g})
    return out
