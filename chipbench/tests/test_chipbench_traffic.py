"""The traffic generator: seeded, in range, the same work for every seed."""
import collections

import numpy as np
import pytest

from chipbench.harness import Manifest

M = Manifest()
GEN = M.module("traffic", "gen")
VOCAB = 151936


def _mix(name):
    mix = dict(M.json("traffic", name))
    mix["n_requests"] = 4 * mix["block"]     # a few blocks are enough
    return mix


@pytest.mark.parametrize("name", ["decode", "shared-prefix"])
def test_same_seed_same_requests(name):
    a = GEN.requests(_mix(name), 2 ** 33 + 7, VOCAB)
    b = GEN.requests(_mix(name), 2 ** 33 + 7, VOCAB)
    c = GEN.requests(_mix(name), 2 ** 33 + 8, VOCAB)
    key = lambda rs: [(r["uid"], r["prompt"].tobytes(), r["max_new"],
                       r["arrival"]) for r in rs]
    assert key(a) == key(b)
    assert key(a) != key(c)


@pytest.mark.parametrize("name", ["decode", "shared-prefix"])
def test_every_seed_and_block_carries_the_same_work(name):
    mix = _mix(name)
    m = mix["block"]
    per_seed = []
    for seed in (1, 99, 2 ** 40):
        rs = GEN.requests(mix, seed, VOCAB)
        blocks = [rs[i:i + m] for i in range(0, len(rs), m)]
        shapes = [sorted((len(r["prompt"]), r["max_new"], r["group"])
                         for r in b) for b in blocks]
        assert all(s == shapes[0] for s in shapes)
        gaps = np.diff([0.0] + [r["arrival"] for r in rs])
        assert sorted(gaps[:m]) == pytest.approx(sorted(gaps[m:2 * m]))
        # every seed: the same requests' shapes in the same order
        per_seed.append([(len(r["prompt"]), r["max_new"], r["group"],
                          r["arrival"]) for r in rs])
    assert all(p == per_seed[0] for p in per_seed)


def test_lengths_stay_in_their_ranges():
    mix = _mix("shared-prefix")
    rs = GEN.requests(mix, 5, VOCAB)
    lo, hi = mix["prompt_len"]
    sys_len = mix["system_prompts"]["len"]
    assert all(lo <= len(r["prompt"]) - sys_len <= hi for r in rs)
    olo, ohi = mix["output_len"]
    assert all(olo <= r["max_new"] <= ohi for r in rs)
    assert all(0 <= t < VOCAB for r in rs for t in r["prompt"][:64])
    dec = GEN.requests(_mix("decode"), 5, VOCAB)
    lens = {len(r["prompt"]) for r in dec}
    assert lens == set(GEN.prompt_lengths(_mix("decode")))
    assert lens == set(range(128, 513, 64))
    assert all(r["arrival"] == 0.0 for r in dec)


def test_group_popularity_follows_the_zipf_pick():
    mix = _mix("shared-prefix")
    sp = mix["system_prompts"]
    rs = GEN.requests(mix, 11, VOCAB)
    got = collections.Counter(r["group"] for r in rs)
    want = GEN.zipf_counts(mix["block"], sp["count"], sp["zipf_s"])
    assert [got[g] for g in range(sp["count"])] == list(4 * want)
    # requests of one group share its whole system prompt
    firsts = {}
    for r in rs:
        head = r["prompt"][:sp["len"]].tobytes()
        assert firsts.setdefault(r["group"], head) == head
    assert len(set(firsts.values())) == sp["count"]


def test_zipf_counts():
    c = GEN.zipf_counts(64, 16, 1.0)
    assert c.sum() == 64 and all(np.diff(c) <= 0)
    # weights 1/g over H_16 = 3.3807: the head gets 64 / 3.38 ~ 18.9
    assert c[0] == 19 and c[-1] == 1


def test_poisson_gaps_have_the_mean_rate():
    mix = _mix("shared-prefix")
    rs = GEN.requests(mix, 3, VOCAB)
    span = rs[-1]["arrival"]
    assert len(rs) / span == pytest.approx(mix["rate_per_step"], rel=0.05)
