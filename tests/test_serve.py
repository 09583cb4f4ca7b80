"""Continuous-batching serving subsystem: paged KV cache allocator
invariants, block-table attention vs the dense cache path, greedy output
bit-identity across scheduling (arrival order, batch size, scheduler
choice, solo oracle), the jit-recompile cap, the prefill key-split fix,
arrival-trace determinism, and the serve/* bench rows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import dataclasses

from repro.core.config import ArchConfig, AttnConfig
from repro.distributed.sharding import split_tree
from repro.launch.serve import ServingLoop
from repro.models import attention as attn
from repro.models import build_model
from repro.models import transformer as tfm
from repro.serve import (CohortScheduler, ContinuousScheduler, PagedKVCache,
                         Request, block_hashes, make_trace, next_pow2)


def _cfg(vocab=128):
    return ArchConfig(name="sv", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab=vocab,
                      attn=AttnConfig(chunk=16))


@pytest.fixture(scope="module")
def served():
    cfg = _cfg()
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(1)))
    return cfg, model, params


def _reqs(cfg, lens, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        (int(n),)).astype(np.int32),
                    max_new=int(m))
            for i, (n, m) in enumerate(zip(lens, max_new))]


def _continuous(cfg, params, batch):
    return ContinuousScheduler(cfg, params, batch=batch, max_seq=64,
                               block_len=8)


# ---------------------------------------------------------------------------
# PagedKVCache allocator
# ---------------------------------------------------------------------------

def test_paged_cache_alloc_free_reuse():
    cache = PagedKVCache(_cfg(), batch=2, total_tokens=64, max_seq=32,
                         block_len=8)
    n_free0 = cache.free_blocks
    ids = cache.admit(0, prefill_tokens=16, lifetime_tokens=24)
    assert len(ids) == 2 and 0 not in ids          # block 0 is scratch
    assert cache.free_blocks == n_free0 - 2
    assert cache.reserved_blocks == 1              # 24 tokens -> 3 blocks
    assert list(cache.tables[0, :2]) == ids

    cache.append(0, 16)                            # crosses into block 3
    assert cache.reserved_blocks == 0
    assert cache.free_blocks == n_free0 - 3
    cache.append(0, 17)                            # same block: no alloc
    assert cache.free_blocks == n_free0 - 3

    freed = cache.free_slot(0)
    assert len(freed) == 3 and set(ids) <= set(freed)
    assert cache.free_blocks == n_free0
    assert cache.used_blocks == 0
    assert (cache.tables[0] == -1).all()
    # freed blocks' device position rows were cleared
    pos = np.asarray(cache.state.pos)
    for b in freed:
        assert (pos[b] == -1).all()

    # LIFO reuse: the next admission gets just-freed blocks back
    ids2 = cache.admit(1, prefill_tokens=8, lifetime_tokens=8)
    assert ids2[0] in freed


def test_paged_cache_admission_when_full():
    cache = PagedKVCache(_cfg(), batch=4, total_tokens=32, max_seq=32,
                         block_len=8)                  # 4 usable blocks
    assert cache.can_admit(24)
    cache.admit(0, prefill_tokens=16, lifetime_tokens=24)  # 3 blocks
    assert cache.can_admit(8)
    assert not cache.can_admit(16)      # only 1 unreserved block left
    cache.admit(1, prefill_tokens=8, lifetime_tokens=8)
    assert not cache.can_admit(1)       # arena exhausted
    cache.free_slot(0)
    assert cache.can_admit(24)          # blocks + reservation returned
    # over-reserving beyond the guarantee is an error, not a deadlock
    with pytest.raises(RuntimeError):
        cache.admit(2, prefill_tokens=32, lifetime_tokens=64)


def test_paged_cache_append_guards():
    cache = PagedKVCache(_cfg(), batch=1, total_tokens=32, max_seq=32,
                         block_len=8)
    cache.admit(0, prefill_tokens=8, lifetime_tokens=8)   # no reservation
    with pytest.raises(RuntimeError, match="reserved lifetime"):
        cache.append(0, 8)              # needs a block it never reserved


def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 8, 9, 17)] == \
        [1, 2, 4, 8, 16, 32]


def test_free_slot_releases_midprefill_reservation():
    """Regression: cancelling a slot between admission and its first
    append must return the lifetime-*reserved* (never-allocated) blocks
    too — repeated admit-then-cancel at full reservation pressure must
    not leak a single block."""
    cache = PagedKVCache(_cfg(), batch=2, total_tokens=64, max_seq=64,
                         block_len=8)
    free0 = cache.free_blocks
    for _ in range(4 * cache.n_blocks):      # far past arena capacity
        cache.admit(0, prefill_tokens=8, lifetime_tokens=64)
        cache.free_slot(0)                   # cancelled mid-prefill
        assert cache.free_blocks == free0
        assert cache.reserved_blocks == 0
        assert cache.used_blocks == 0
    # same invariant through the shared-admission path
    cache2 = PagedKVCache(_cfg(), batch=2, total_tokens=64, max_seq=64,
                          block_len=8, prefix_cache=True)
    toks = np.arange(40, dtype=np.int32)
    free0 = cache2.free_blocks
    for _ in range(4 * cache2.n_blocks):
        cache2.admit_shared(0, toks, 64, max_match_rows=32)
        cache2.free_slot(0)
        assert cache2.free_blocks + cache2.evictable_blocks == free0
        assert cache2.reserved_blocks == 0
        assert cache2.used_blocks == 0


# ---------------------------------------------------------------------------
# Prefix sharing: content addressing, refcounts, CoW, retention
# ---------------------------------------------------------------------------

def test_block_hashes_chain_property():
    toks = np.arange(32, dtype=np.int32)
    h2 = block_hashes(toks[:16], 2, 8)
    h4 = block_hashes(toks, 4, 8)
    assert h4[:2] == h2                     # prefix of hashes = hash of prefix
    assert len(set(h4)) == 4
    # a flipped token in block 0 changes every chain hash after it
    other = toks.copy()
    other[0] += 1
    assert all(a != b for a, b in zip(block_hashes(other, 4, 8), h4))
    # a flipped token in block 2 leaves blocks 0-1 alone
    other2 = toks.copy()
    other2[16] += 1
    assert block_hashes(other2, 4, 8)[:2] == h2
    with pytest.raises(ValueError):
        block_hashes(toks[:10], 2, 8)


def _prefix_cache(batch=3, total=80, max_seq=48):
    return PagedKVCache(_cfg(), batch=batch, total_tokens=total,
                        max_seq=max_seq, block_len=8, prefix_cache=True)


def test_admit_shared_maps_registered_prefix_by_reference():
    cache = _prefix_cache()
    toks = np.arange(100, 132, dtype=np.int32)      # 4 full blocks
    cache.admit(0, prefill_tokens=32, lifetime_tokens=32)
    producer = list(cache._slot_blocks[0])
    cache.register_prefix(0, toks, 32)
    assert cache.match_prefix(toks, 32) == producer

    # consumer with the same 32-token prefix + an 8-token private tail
    toks2 = np.concatenate([toks, np.arange(8, dtype=np.int32)])
    m = cache.admit_shared(1, toks2, lifetime_tokens=48, max_match_rows=32)
    assert m == 32
    assert cache._slot_blocks[1] == producer        # mapped, not copied
    assert all(cache._ref[b] == 2 for b in producer)
    # reservation shrank by the 4 matched blocks: 48 tokens = 6 blocks
    assert cache._slot_reserved[1] == 2
    assert cache.hit_tokens == 32 and cache.miss_tokens == 8
    assert cache.cache_hit_ratio == pytest.approx(32 / 40)

    # granule rounding: a 4-block match capped to 2-chunk (16-row) units
    cache.free_slot(1)
    m = cache.admit_shared(1, toks2, lifetime_tokens=48,
                           max_match_rows=32, granule_rows=16)
    assert m == 32                                  # 32 is a 16-multiple
    cache.free_slot(1)
    m = cache.admit_shared(2, toks2[:28], lifetime_tokens=28,
                           max_match_rows=24, granule_rows=16)
    assert m == 16                                  # 3 blocks round to 2


def test_free_slot_retains_registered_blocks_until_evicted():
    cache = _prefix_cache(batch=2, total=40, max_seq=40)  # 6 blocks
    toks = np.arange(16, dtype=np.int32)
    cache.admit(0, prefill_tokens=16, lifetime_tokens=16)
    shared = list(cache._slot_blocks[0])
    cache.register_prefix(0, toks, 16)
    free_before = cache.free_blocks
    cache.free_slot(0)
    # registered blocks park in the evictable pool, not the free list
    assert cache.free_blocks == free_before
    assert cache.evictable_blocks == 2
    # a later match revives them by reference
    m = cache.admit_shared(0, toks, lifetime_tokens=16, max_match_rows=16)
    assert m == 16 and cache.evictable_blocks == 0
    assert cache._slot_blocks[0] == shared
    cache.free_slot(0)

    # exhausting the free list forces LRU eviction of the retained pool
    cache.admit(1, prefill_tokens=40, lifetime_tokens=40)   # 5 blocks
    assert cache.evictable_blocks < 2       # at least one was reclaimed
    evicted = [b for b in shared if b in cache._slot_blocks[1]]
    assert evicted                          # reused for the new tenant
    assert cache.match_prefix(toks, 16) == []   # registration dropped
    pos = np.asarray(cache.state.pos)
    # eviction scrubbed the reclaimed rows before reuse
    for b in evicted:
        assert (pos[b] == -1).all()


def test_copy_on_write_on_fork():
    cache = _prefix_cache(batch=2, total=80, max_seq=48)
    cache.admit(0, prefill_tokens=20, lifetime_tokens=20)  # partial block 2
    src_blocks = list(cache._slot_blocks[0])
    # give the shared partial block recognizable device content
    pos = np.array(cache.state.pos)
    pos[src_blocks[-1], :4] = np.arange(16, 20)
    cache.state = tfm.PagedState(k=cache.state.k, v=cache.state.v,
                                 pos=jnp.asarray(pos))

    cache.fork_slot(0, 1, src_len=20, lifetime_tokens=28)
    assert cache._slot_blocks[1] == src_blocks
    assert all(cache._ref[b] == 2 for b in src_blocks)
    # 28 tokens = 4 blocks; 3 mapped -> 1 lifetime + 1 CoW reserve
    assert cache._slot_reserved[1] == 2

    cache.append(1, 20)         # lands in the shared partial block
    forked = cache._slot_blocks[1]
    assert forked[:2] == src_blocks[:2]     # full blocks still shared
    assert forked[2] != src_blocks[2]       # partial block went private
    assert cache._ref[src_blocks[2]] == 1   # src keeps the original
    assert cache._slot_blocks[0] == src_blocks
    assert cache.tables[1, 2] == forked[2]
    assert cache._slot_reserved[1] == 1     # CoW drew from the reservation
    # the copy carried the device rows
    pos = np.asarray(cache.state.pos)
    np.testing.assert_array_equal(pos[forked[2]], pos[src_blocks[2]])
    assert (pos[forked[2], :4] == np.arange(16, 20)).all()
    # freeing the fork returns only its private block to the free list
    free_before = cache.free_blocks
    cache.free_slot(1)
    assert cache.free_blocks == free_before + 1
    assert all(cache._ref[b] == 1 for b in src_blocks)


def test_reset_prefix_cache_reclaims_retained_pool():
    cache = _prefix_cache(batch=1, total=40, max_seq=40)
    toks = np.arange(16, dtype=np.int32)
    cache.admit_shared(0, toks, 16, max_match_rows=16)
    cache.extend_to(0, 16)          # shared admission allocates lazily
    cache.register_prefix(0, toks, 16)
    cache.free_slot(0)
    assert cache.evictable_blocks == 2 and cache.miss_tokens == 16
    free_before = cache.free_blocks
    cache.reset_prefix_cache()
    assert cache.evictable_blocks == 0
    assert cache.free_blocks == free_before + 2
    assert cache.hit_tokens == 0 and cache.miss_tokens == 0
    assert cache.match_prefix(toks, 16) == []


# ---------------------------------------------------------------------------
# Block-table attention vs the dense cache path
# ---------------------------------------------------------------------------

def test_attend_paged_matches_attend_decode():
    """Gathering (k, v, pos) through a block table must reproduce the
    dense ragged-decode attention bit-for-bit."""
    rng = np.random.default_rng(0)
    B, W, KV, HP, HD, BL = 2, 16, 2, 4, 8, 4
    k = rng.standard_normal((B, W, KV, HD)).astype(np.float32)
    v = rng.standard_normal((B, W, KV, HD)).astype(np.float32)
    q = rng.standard_normal((B, 1, HP, HD)).astype(np.float32)
    # ragged: slot 0 holds 10 rows, slot 1 holds 6
    pos = np.full((B, W), -1, np.int32)
    pos[0, :10] = np.arange(10)
    pos[1, :6] = np.arange(6)
    q_position = jnp.asarray([10, 6], jnp.int32)
    idx_map = attn.kv_index_map(HP, KV, HP)

    dense = attn.attend_decode(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v), jnp.asarray(pos), idx_map,
                               q_position=q_position)

    # scatter the same rows into a block arena: slot 0 -> blocks 1..3,
    # slot 1 -> blocks 4..5 (table padded with -1)
    n_blocks = 7
    kb = np.zeros((n_blocks, BL, KV, HD), np.float32)
    vb = np.zeros((n_blocks, BL, KV, HD), np.float32)
    pb = np.full((n_blocks, BL), -1, np.int32)
    table = np.full((B, 4), -1, np.int32)
    table[0, :3] = [1, 2, 3]
    table[1, :2] = [4, 5]
    for s in range(B):
        for j, b in enumerate(t for t in table[s] if t >= 0):
            kb[b] = k[s, j * BL:(j + 1) * BL]
            vb[b] = v[s, j * BL:(j + 1) * BL]
            pb[b] = pos[s, j * BL:(j + 1) * BL]
    # poison the scratch block: a correct gather never attends it
    kb[0] += 100.0
    pb[0] = 0

    paged = attn.attend_paged(jnp.asarray(q), jnp.asarray(kb[None]),
                              jnp.asarray(vb[None]), jnp.asarray(pb),
                              jnp.asarray(table), idx_map, layer=0,
                              q_position=q_position)
    np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))


def test_forward_paged_decode_rejects_unpaged_family():
    cfg = ArchConfig(name="ssm", family="ssm", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64)
    assert build_model(cfg).decode_paged is None
    paged = tfm.init_paged_state(_cfg(), 2, 8)
    with pytest.raises(NotImplementedError):
        tfm.forward_paged_decode({}, cfg, jnp.zeros((1, 1), jnp.int32),
                                 paged, jnp.zeros((1, 1), jnp.int32),
                                 jnp.zeros((1,), jnp.int32))


# ---------------------------------------------------------------------------
# Greedy bit-identity across scheduling
# ---------------------------------------------------------------------------

def test_continuous_matches_solo_oracle_and_orderings(served):
    """Continuous batching must not change greedy outputs: same tokens
    for every request whether served alone, in a different arrival
    order, or at a different batch size."""
    cfg, model, params = served
    reqs = lambda: _reqs(cfg, lens=(12, 7, 9), max_new=(3, 4, 3))

    base = _continuous(cfg, params, 2).run(reqs())
    oracle = {}
    for r in reqs():
        oracle.update(_continuous(cfg, params, 1).run([r]))
    assert base == oracle

    reordered = _continuous(cfg, params, 2).run(reqs()[::-1])
    assert reordered == base

    wider = _continuous(cfg, params, 3).run(reqs())
    assert wider == base

    # teacher-forcing reference for one member
    r0 = reqs()[0]
    toks = list(r0.prompt)
    for _ in range(r0.max_new):
        logits = model.forward(
            params, {"tokens": jnp.asarray([toks]),
                     "labels": jnp.zeros((1, len(toks)), jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, -1, :cfg.vocab])))
    assert base[0] == toks[len(r0.prompt):]


def test_continuous_matches_cohort_equal_lengths(served):
    """For equal-length prompts (no cohort padding) the two schedulers
    are numerically identical under greedy decoding."""
    cfg, _, params = served
    mk = lambda: _reqs(cfg, lens=(10, 10), max_new=(3, 3), seed=2)
    cont = _continuous(cfg, params, 2).run(mk())
    coh = CohortScheduler(cfg, params, batch=2).run(mk())
    assert cont == coh


def test_continuous_slot_refill_under_arrivals(served):
    """More requests than slots + staggered arrivals: every request is
    served, outputs still match the solo oracle, and the arena drains."""
    cfg, _, params = served
    mk = lambda: [Request(uid=i, prompt=p.prompt, max_new=p.max_new,
                          arrival=float(i))
                  for i, p in enumerate(
                      _reqs(cfg, lens=(11, 6, 9, 7, 8), max_new=(2, 4, 3,
                                                                 2, 3),
                            seed=3))]
    sched = _continuous(cfg, params, 2)
    out = sched.run(mk())
    assert set(out) == set(range(5))
    oracle = {}
    for r in mk():
        r.arrival = 0.0
        oracle.update(_continuous(cfg, params, 1).run([r]))
    assert out == oracle
    assert sched.cache.used_blocks == 0
    assert sched.cache.free_blocks == sched.cache.n_blocks - 1
    snap = {row["name"]: row for row in sched.metrics.snapshot()}
    assert snap["serve.requests_total"]["value"] == 5
    assert snap["serve.tokens_total"]["value"] == 2 + 4 + 3 + 2 + 3


# ---------------------------------------------------------------------------
# Satellite fixes: recompile cap + prefill key split
# ---------------------------------------------------------------------------

def test_cohort_budget_bucketing_caps_recompiles(served):
    """Prompt lengths whose KV budgets land in the same power-of-two
    bucket must share one compiled (prefill, decode) pair."""
    cfg, _, params = served
    sched = CohortScheduler(cfg, params, batch=1, max_new=4)
    sched.run(_reqs(cfg, lens=(20,), max_new=(2,), seed=4))
    sched.run(_reqs(cfg, lens=(24,), max_new=(2,), seed=5))
    # budgets 25 and 29 both bucket to 32 -> one compiled pair
    assert len(sched._fns) == 1


def test_cohort_prefill_splits_sampling_key(served):
    """Regression: the prefill sample must consume a split of the loop
    key, not the key itself — a prefill-only run must advance the key."""
    cfg, _, params = served
    sched = CohortScheduler(cfg, params, batch=1, seed=7)
    key0 = np.asarray(sched.key).copy()
    sched.run(_reqs(cfg, lens=(8,), max_new=(1,), seed=6),
              temperature=1.0, max_steps=1)
    assert not np.array_equal(np.asarray(sched.key), key0)
    # and two consecutive prefill-only runs draw from different streams
    out1 = sched.run(_reqs(cfg, lens=(8,), max_new=(1,), seed=6),
                     temperature=1.0, max_steps=1)
    out2 = sched.run(_reqs(cfg, lens=(8,), max_new=(1,), seed=6),
                     temperature=1.0, max_steps=1)
    assert not np.array_equal(np.asarray(sched.key), key0)
    assert out1.keys() == out2.keys()


def test_continuous_sampling_is_scheduling_independent(served):
    """Per-request fold_in keys: sampled (temperature > 0) outputs don't
    depend on batch size or arrival order."""
    cfg, _, params = served
    mk = lambda: _reqs(cfg, lens=(9, 12, 7), max_new=(3, 3, 3), seed=8)
    a = ContinuousScheduler(cfg, params, batch=3, max_seq=64, block_len=8,
                            seed=11).run(mk(), temperature=0.7)
    b = ContinuousScheduler(cfg, params, batch=1, max_seq=64, block_len=8,
                            seed=11).run(mk()[::-1], temperature=0.7)
    assert a == b


# ---------------------------------------------------------------------------
# Arrival traces + launch wrapper + bench rows
# ---------------------------------------------------------------------------

def test_traces_edge_cases_deterministic():
    """rate=0, burst=1 and single-request traces are deterministic and
    (for rate=0) identical across arrival kinds and seeds."""
    for kind in ("uniform", "poisson", "bursty"):
        for seed in (0, 7):
            tr = make_trace(kind, 4, vocab=64, rate=0.0, seed=seed)
            assert [r.arrival for r in tr] == [0.0] * 4
    # rate=0 draws nothing from the RNG: prompts match the rate>0 trace
    a = make_trace("poisson", 4, vocab=64, rate=0.0, seed=3)
    b = make_trace("poisson", 4, vocab=64, rate=0.5, seed=3)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # burst=1 degenerates to poisson exactly (same draws, same gaps)
    p = make_trace("poisson", 6, vocab=64, rate=0.5, seed=5)
    b1 = make_trace("bursty", 6, vocab=64, rate=0.5, burst=1, seed=5)
    assert [r.arrival for r in p] == [r.arrival for r in b1]
    # single-request traces replay identically
    s1 = make_trace("bursty", 1, vocab=64, rate=0.5, burst=4, seed=9)
    s2 = make_trace("bursty", 1, vocab=64, rate=0.5, burst=4, seed=9)
    assert len(s1) == 1 and s1[0].arrival == s2[0].arrival
    assert np.array_equal(s1[0].prompt, s2[0].prompt)
    assert make_trace("uniform", 0, vocab=64) == []
    # invalid inputs fail loudly even when rate=0 would trivialize gaps
    with pytest.raises(ValueError):
        make_trace("laplace", 2, vocab=64, rate=0.0)
    with pytest.raises(ValueError):
        make_trace("bursty", 2, vocab=64, rate=0.0, burst=0)
    with pytest.raises(ValueError):
        make_trace("poisson", 2, vocab=64, rate=-1.0)
    with pytest.raises(ValueError):
        make_trace("uniform", -1, vocab=64)


def test_traces_shared_prefix_groups():
    plain = make_trace("uniform", 6, vocab=64, rate=0.5, seed=4)
    shared = make_trace("uniform", 6, vocab=64, rate=0.5, seed=4,
                        prefix_len=16, prefix_group=3)
    # every request in a group shares the same 16 leading tokens
    for g in (0, 1):
        heads = [shared[g * 3 + i].prompt[:16] for i in range(3)]
        assert all(np.array_equal(heads[0], h) for h in heads[1:])
    assert not np.array_equal(shared[0].prompt[:16], shared[3].prompt[:16])
    # tails and arrivals replay the prefix-free trace exactly (prefixes
    # are drawn after the prompts, so the RNG stream is unperturbed)
    for x, y in zip(plain, shared):
        assert np.array_equal(x.prompt, y.prompt[16:])
        assert x.arrival == y.arrival and x.max_new == y.max_new


def test_traces_deterministic_and_shaped():
    a = make_trace("poisson", 8, vocab=64, rate=0.5, seed=3)
    b = make_trace("poisson", 8, vocab=64, rate=0.5, seed=3)
    assert [r.arrival for r in a] == [r.arrival for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    arr = [r.arrival for r in a]
    assert arr == sorted(arr) and arr[-1] > 0
    # same seed, different arrival process -> identical request shapes
    u = make_trace("uniform", 8, vocab=64, rate=0.5, seed=3)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, u))
    bursty = make_trace("bursty", 8, vocab=64, rate=0.5, burst=4, seed=3)
    assert bursty[0].arrival == bursty[1].arrival    # burst members co-arrive
    with pytest.raises(ValueError):
        make_trace("laplace", 4, vocab=64)


def test_serving_loop_falls_back_to_cohort():
    cfg = ArchConfig(name="ssm", family="ssm", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    loop = ServingLoop(cfg, params, batch=2, scheduler="continuous")
    assert loop.scheduler_kind == "cohort"
    out = loop.run(_reqs(cfg, lens=(8, 8), max_new=(2, 2)))
    assert all(len(v) == 2 for v in out.values())


def test_serve_scenarios_registered_and_runnable(served):
    from repro.bench.runner import RunOptions, project_scenario, sweep
    from repro.bench.scenario import ServeScenario, get_scenario, scenarios

    names = [s.name for s in scenarios(tag="serve")]
    for arrival in ("uniform", "poisson", "bursty"):
        for sched in ("continuous", "cohort"):
            assert f"serve/{arrival}/{sched}" in names
    # serving cells are excluded from the smoke kernel sweep
    assert not [s for s in scenarios(smoke=True) if s.is_serving]
    with pytest.raises(ValueError):
        project_scenario(get_scenario("serve/uniform/continuous"), "A100")

    sc = ServeScenario(
        name="serve/test/tiny", shape=(2, 3),
        workload={"scheduler": "continuous", "arrival": "uniform",
                  "n_requests": 3, "batch": 2, "rate": 1.0,
                  "prompt_lens": [5, 10], "max_new": [2, 3], "seed": 0,
                  "block_len": 8},
        tags=("serve",), section="serve")
    report = sweep([sc], chips=["A100"], opts=RunOptions(emit=None))
    rows = [r for r in report.results if r.scenario == "serve/test/tiny"]
    assert len(rows) == 1               # measured only: no projection rows
    m = rows[0].metrics
    assert rows[0].kind == "measured"
    assert m["us_median"] > 0 and len(m["times_us"]) >= 2
    assert m["tokens"] > 0 and m["requests"] == 3
    assert 0 < m["occupancy_mean"] <= 1
    assert m["tokens_per_s"] > 0


# ---------------------------------------------------------------------------
# Chunked prefill + prefix sharing
# ---------------------------------------------------------------------------

def _chunked(cfg, params, batch, *, chunk=16, prefix=False):
    return ContinuousScheduler(cfg, params, batch=batch, max_seq=64,
                               block_len=8, chunk_tokens=chunk,
                               prefix_cache=prefix)


def test_chunked_matches_chunked_solo_oracle_two_orders(served):
    """Chunked prefill must not change greedy outputs vs serving each
    request alone through the same chunked path, under both FIFO and
    reversed arrival orders, with and without prefix sharing."""
    cfg, _, params = served
    base = _reqs(cfg, lens=(21, 7, 12), max_new=(3, 4, 3), seed=12)

    def mk(order):
        arr = {i: float(j) for j, i in enumerate(order)}
        return [Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                        arrival=arr[r.uid]) for r in base]

    solo = _chunked(cfg, params, 1)
    oracle = {}
    for r in mk([0, 1, 2]):
        r.arrival = 0.0
        oracle.update(solo.run([r]))
    for prefix in (False, True):
        fifo = _chunked(cfg, params, 2, prefix=prefix).run(mk([0, 1, 2]))
        rev = _chunked(cfg, params, 2, prefix=prefix).run(mk([2, 1, 0]))
        assert fifo == oracle
        assert rev == oracle


@pytest.mark.parametrize("chunk", [None, 16])
def test_requested_logits_are_the_rows_tokens_came_from(served, chunk):
    """A request that asks for its logits gets one row per output token,
    each the row its greedy token is the argmax of; one that does not ask
    records nothing."""
    cfg, _, params = served
    reqs = _reqs(cfg, lens=(21, 7, 12), max_new=(3, 4, 3), seed=13)
    for r in reqs[:2]:
        r.logits = []
    ContinuousScheduler(cfg, params, batch=2, max_seq=64, block_len=8,
                        chunk_tokens=chunk).run(reqs)
    for r in reqs[:2]:
        assert len(r.logits) == len(r.out_tokens) == r.max_new
        assert [int(jnp.argmax(row)) for row in r.logits] == r.out_tokens
        assert all(row.shape == (cfg.vocab,) for row in r.logits)
    assert reqs[2].logits is None


#: the children a ``serve.step`` span may hold
STEP_CHILDREN = {"serve.admit", "serve.prefill", "serve.prefill_chunk",
                 "serve.decode_step", "serve.device_wait", "serve.sample",
                 "serve.readback"}


@pytest.mark.parametrize("chunk,temperature", [(None, 0.0), (16, 0.0),
                                               (16, 0.7)])
def test_scheduler_span_tree_under_a_profiler_session(served, chunk,
                                                      temperature, tmp_path):
    """Under a profiler session every scheduler iteration is one
    ``serve.step`` whose phases are its children, no longer than it in
    sum; readback counts every token appended.  Without a session nothing
    is recorded."""
    from repro.obs.trace import tracer
    cfg, _, params = served
    reqs = _reqs(cfg, lens=(21, 7, 40, 12), max_new=(3, 5, 2, 4), seed=5)
    for r, a in zip(reqs, (0, 0, 2, 3)):
        r.arrival = a
    sched = ContinuousScheduler(cfg, params, batch=2, max_seq=64,
                                block_len=8, chunk_tokens=chunk,
                                prefix_cache=chunk is not None)
    tr = tracer()
    tr.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = sched.run(reqs, temperature=temperature)
    finally:
        jax.profiler.stop_trace()
    spans = tr.spans()
    tr.clear()

    steps = {s.span_id: s for s in spans if s.name == "serve.step"}
    assert steps
    for st in steps.values():
        assert set(st.attrs) == {"active", "queued", "prefilling"}
    children = [s for s in spans if s.name != "serve.step"]
    assert all(s.parent_id in steps for s in children)
    assert {s.name for s in children} <= STEP_CHILDREN
    for sid, st in steps.items():
        mine = [s for s in children if s.parent_id == sid]
        assert sum(s.dur_us for s in mine) <= st.dur_us
        if any(s.name == "serve.decode_step" for s in mine):
            assert {"serve.device_wait", "serve.readback"} <= {
                s.name for s in mine}
    names = {s.name for s in children}
    prefill = "serve.prefill" if chunk is None else "serve.prefill_chunk"
    assert {"serve.admit", prefill, "serve.decode_step", "serve.device_wait",
            "serve.readback"} <= names
    assert ("serve.sample" in names) == (temperature > 0)
    waits = {s.attrs["on"] for s in children if s.name == "serve.device_wait"}
    assert waits == {"decode", "prefill" if chunk is None else "chunk"}
    admits = [s for s in children if s.name == "serve.admit"]
    assert sorted(s.attrs["uid"] for s in admits) == [r.uid for r in reqs]
    assert all(s.attrs["waited_ms"] >= 0 and s.attrs["matched"] >= 0
               for s in admits)
    readback = [s for s in children if s.name == "serve.readback"]
    assert sum(s.attrs["n_tokens"] for s in readback) == \
        sum(len(v) for v in out.values())
    assert sum(s.attrs["finished"] for s in readback) == len(reqs)
    assert all(s.attrs["transfers"] in (0, 1) for s in readback)
    if temperature == 0:
        assert all(s.attrs["transfers"] == 1 for s in readback)

    again = _reqs(cfg, lens=(21, 7, 40, 12), max_new=(3, 5, 2, 4), seed=5)
    sched.run(again, temperature=temperature)
    assert tr.spans() == []


@pytest.mark.parametrize("chunk", [None, 16])
def test_readback_is_one_copy_per_dispatch(served, chunk, monkeypatch):
    """A dispatch's tokens reach the host in one copy: the scheduler
    indexes no device array while it serves, every ``serve.readback``
    span (decode steps and first tokens alike) records one transfer, and
    the tokens are the solo oracle's."""
    import sys
    from jax._src.array import ArrayImpl
    from repro.obs.trace import tracer
    from repro.serve import scheduler
    cfg, _, params = served
    reqs = lambda: [Request(uid=r.uid, prompt=r.prompt, max_new=r.max_new,
                            arrival=float(r.uid))
                    for r in _reqs(cfg, lens=(21, 7, 12, 9),
                                   max_new=(3, 5, 2, 4), seed=17)]

    def make(batch):
        return ContinuousScheduler(cfg, params, batch=batch, max_seq=64,
                                   block_len=8, chunk_tokens=chunk,
                                   prefix_cache=chunk is not None)

    oracle = {}
    for r in reqs():
        r.arrival = 0.0
        oracle.update(make(1).run([r]))

    indexed = []
    getitem = ArrayImpl.__getitem__

    def counting(self, idx):
        if sys._getframe(1).f_code.co_filename == scheduler.__file__:
            indexed.append(idx)
        return getitem(self, idx)

    monkeypatch.setattr(ArrayImpl, "__getitem__", counting)
    tr = tracer()
    tr.clear()
    tr.enable()
    try:
        out = make(2).run(reqs())
    finally:
        tr.disable()
    spans = tr.spans()
    tr.clear()

    assert indexed == []
    readback = [s for s in spans if s.name == "serve.readback"]
    n_decode = sum(s.name == "serve.decode_step" for s in spans)
    assert len(readback) == n_decode + len(oracle)
    assert all(s.attrs["transfers"] == 1 for s in readback)
    assert out == oracle


def _event_order_requests(cfg):
    """Four requests for two slots: 0 and 1 share a 16-token head, 1
    falls due while 0 prefills, 3 arrives after the batch has drained."""
    rng = np.random.default_rng(31)
    head = rng.integers(0, cfg.vocab, (16,)).astype(np.int32)
    tail = lambda n: rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
    prompts = (np.concatenate([head, tail(4)]), np.concatenate([head, tail(6)]),
               tail(9), tail(12))
    return [Request(uid=i, prompt=p, max_new=m, arrival=a)
            for i, (p, m, a) in enumerate(zip(prompts, (3, 2, 2, 2),
                                              (0.0, 1.0, 2.0, 12.0)))]


def _event_order(cfg, params, chunk):
    """Serve the event-order trace with the tracer on; returns the outputs
    and, for each ``serve.step`` in order, its occupancy attributes and its
    children in start order as (name, uid, start, n_tokens, on)."""
    from repro.obs.trace import tracer
    sched = ContinuousScheduler(cfg, params, batch=2, max_seq=64,
                                block_len=8, prefix_cache=chunk is not None)
    tr = tracer()
    tr.clear()
    tr.enable()
    try:
        out = sched.run(_event_order_requests(cfg), max_steps=8)
    finally:
        tr.disable()
    spans = sorted(tr.spans(), key=lambda s: s.t0_us)
    tr.clear()
    steps = []
    for st in (s for s in spans if s.name == "serve.step"):
        kids = [(s.name[len("serve."):],) + tuple(
                    s.attrs.get(k) for k in ("uid", "start", "n_tokens", "on"))
                for s in spans if s.parent_id == st.span_id]
        steps.append(((st.attrs["active"], st.attrs["queued"],
                       st.attrs["prefilling"]), kids))
    return out, steps


#: ``_event_order``'s iterations as the scheduler ran them: ((active,
#: queued, prefilling), children) a ``serve.step``
EVENT_ORDER = {None: [
    ((0, 1, 0), [("admit", 0, None, None, None),
                 ("prefill", 0, 0, 20, None),
                 ("device_wait", None, None, None, "prefill"),
                 ("readback", None, None, 1, None),
                 ("admit", 1, None, None, None),
                 ("prefill", 1, 0, 22, None),
                 ("device_wait", None, None, None, "prefill"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 2, None)]),
    ((1, 1, 0), [("admit", 2, None, None, None),
                 ("prefill", 2, 0, 9, None),
                 ("device_wait", None, None, None, "prefill"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 2, None)]),
    ((0, 0, 0), []),
    ((0, 1, 0), [("admit", 3, None, None, None),
                 ("prefill", 3, 0, 12, None),
                 ("device_wait", None, None, None, "prefill"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 1, None)]),
], 8: [
    ((0, 1, 0), [("admit", 0, None, None, None),
                 ("prefill_chunk", 0, 0, 8, None)]),
    ((0, 1, 1), [("admit", 1, None, None, None),
                 ("prefill_chunk", 0, 8, 8, None)]),
    ((0, 1, 2), [("prefill_chunk", 0, 16, 4, None),
                 ("device_wait", None, None, None, "chunk"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 1, None)]),
    ((1, 1, 1), [("prefill_chunk", 1, 16, 6, None),
                 ("device_wait", None, None, None, "chunk"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 2, None)]),
    ((0, 1, 0), [("admit", 2, None, None, None),
                 ("prefill_chunk", 2, 0, 8, None)]),
    ((0, 0, 1), [("prefill_chunk", 2, 8, 1, None),
                 ("device_wait", None, None, None, "chunk"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 1, None)]),
    ((0, 0, 0), []),
    ((0, 1, 0), [("admit", 3, None, None, None),
                 ("prefill_chunk", 3, 0, 8, None)]),
    ((0, 0, 1), [("prefill_chunk", 3, 8, 4, None),
                 ("device_wait", None, None, None, "chunk"),
                 ("readback", None, None, 1, None),
                 ("decode_step", None, None, None, None),
                 ("device_wait", None, None, None, "decode"),
                 ("readback", None, None, 1, None)]),
]}
#: both modes emit the same tokens here
EVENT_OUTPUTS = {0: [12, 125, 116], 1: [120, 19], 2: [35, 26], 3: [11, 11]}


@pytest.mark.parametrize("chunk", [None, 8], ids=["monolithic", "chunked"])
def test_scheduler_event_order_is_pinned(served, chunk):
    """The order of admissions, prefill dispatches, first-token waits,
    decode steps and readbacks within each iteration, as the virtual clock
    advances and arrivals fall due, is exactly the recorded one, in both
    prefill modes (chunked with the prefix cache), and so are the tokens."""
    cfg, _, params = served
    out, steps = _event_order(cfg, params, chunk)
    assert out == EVENT_OUTPUTS
    assert steps == EVENT_ORDER[chunk]


def test_prefix_sharing_hits_and_is_bit_identical(served):
    """Requests sharing a 32-token prefix: the prefix cache must serve
    later prefills from shared blocks (hit_tokens > 0) without changing
    a single greedy token, and the arena must drain to free + evictable."""
    cfg, _, params = served
    rng = np.random.default_rng(13)
    head = rng.integers(0, cfg.vocab, (32,)).astype(np.int32)

    def mk():
        return [Request(
            uid=i,
            prompt=np.concatenate(
                [head, rng2.integers(0, cfg.vocab, (t,)).astype(np.int32)]),
            max_new=3, arrival=float(i))
            for i, (rng2, t) in enumerate(
                (np.random.default_rng(20 + i), tail)
                for i, tail in enumerate((5, 9, 3, 7)))]

    plain = _chunked(cfg, params, 2)
    shared = _chunked(cfg, params, 2, prefix=True)
    out_plain = plain.run(mk())
    out_shared = shared.run(mk())
    assert out_shared == out_plain
    assert plain.cache.hit_tokens == 0
    assert shared.cache.hit_tokens > 0
    assert 0 < shared.cache.cache_hit_ratio < 1
    c = shared.cache
    assert c.used_blocks == 0 and c.reserved_blocks == 0
    assert c.free_blocks + c.evictable_blocks == c.n_blocks - 1


def test_chunked_jit_cache_bounded(served):
    """Ragged prompt lengths through chunked prefill compile at most one
    chunk fn per pow2 width <= chunk_tokens, independent of the trace."""
    cfg, _, params = served
    sched = _chunked(cfg, params, 2, chunk=16)
    lens = (3, 5, 9, 13, 16, 17, 21, 26, 31, 33)
    sched.run(_reqs(cfg, lens=lens, max_new=[2] * len(lens), seed=14))
    widths = set(sched._chunk_fns)
    assert widths <= {1, 2, 4, 8, 16}
    assert len(widths) <= 5
    # a second ragged run adds no new entries
    sched.run(_reqs(cfg, lens=(4, 11, 27), max_new=(2, 2, 2), seed=15))
    assert set(sched._chunk_fns) == widths


def test_chunked_sampling_is_scheduling_independent(served):
    """Temperature > 0 under chunked prefill + sharing still uses
    per-request keys: outputs are independent of batch and order."""
    cfg, _, params = served
    mk = lambda: _reqs(cfg, lens=(9, 18, 7), max_new=(3, 3, 3), seed=16)
    a = ContinuousScheduler(cfg, params, batch=3, max_seq=64, block_len=8,
                            chunk_tokens=8, seed=21).run(mk(),
                                                         temperature=0.7)
    b = ContinuousScheduler(cfg, params, batch=1, max_seq=64, block_len=8,
                            chunk_tokens=8, prefix_cache=True,
                            seed=21).run(mk()[::-1], temperature=0.7)
    assert a == b


def test_chunk_tokens_validation(served):
    cfg, _, params = served
    with pytest.raises(ValueError, match="multiple of block_len"):
        ContinuousScheduler(cfg, params, batch=1, max_seq=64, block_len=8,
                            chunk_tokens=12)
    with pytest.raises(ValueError, match="multiple of block_len"):
        ContinuousScheduler(cfg, params, batch=1, max_seq=64, block_len=8,
                            chunk_tokens=4)
    vlm_cfg = dataclasses.replace(cfg, n_patches=4)
    with pytest.raises(ValueError, match="vlm"):
        ContinuousScheduler(vlm_cfg, params, batch=1, max_seq=64,
                            block_len=8, chunk_tokens=16)
    # prefix_cache alone implies the finest legal chunk (block_len), so
    # short shared prefixes still land on a match boundary
    sched = ContinuousScheduler(cfg, params, batch=1, max_seq=64,
                                block_len=8, prefix_cache=True)
    assert sched.chunk_tokens == 8 and sched.prefix_cache


def test_serving_loop_auto_disables_chunking():
    """Schedulers without the chunked path (cohort fallback) silently
    drop the chunk/prefix flags instead of crashing."""
    cfg = ArchConfig(name="ssm", family="ssm", n_layers=2, d_model=32,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab=64)
    model = build_model(cfg)
    params, _ = split_tree(model.init(jax.random.PRNGKey(0)))
    loop = ServingLoop(cfg, params, batch=2, scheduler="continuous",
                       chunk_tokens=16, prefix_cache=True)
    assert loop.scheduler_kind == "cohort"
    assert loop.chunk_tokens is None and loop.prefix_cache is False
    out = loop.run(_reqs(cfg, lens=(8, 8), max_new=(2, 2)))
    assert all(len(v) == 2 for v in out.values())


def test_compare_gates_serving_metrics():
    """tokens_per_s (inverted, host-scaled) and cache_hit_ratio (absolute
    band) gate as synthetic scenario:metric rows."""
    from repro.bench.results import BenchReport, BenchResult
    from repro.obs.compare import compare_reports

    def row(tps, hit, us=1000.0):
        return BenchResult(
            scenario="serve/prefix/shared", kernel="serve", shape=[4, 16],
            dtype="bf16", strategy="continuous", chip="TPUv5e",
            metrics={"us_median": us, "times_us": [us] * 5,
                     "tokens_per_s": tps, "cache_hit_ratio": hit},
            kind="measured", section="serve")

    def rep(r):
        rep = BenchReport()
        rep.add(r)
        return rep

    base = rep(row(1000.0, 0.60))
    res = compare_reports(base, rep(row(700.0, 0.61)))
    by = {v.scenario: v for v in res.verdicts}
    assert by["serve/prefix/shared:tokens_per_s"].verdict == "regress"
    assert by["serve/prefix/shared:cache_hit_ratio"].verdict == "pass"
    res = compare_reports(base, rep(row(1300.0, 0.50)))
    by = {v.scenario: v for v in res.verdicts}
    assert by["serve/prefix/shared:tokens_per_s"].verdict == "improve"
    assert by["serve/prefix/shared:cache_hit_ratio"].verdict == "regress"
    assert res.n_regressions == 1
    # a uniformly slower host: us up 2x, tokens/s down 2x -> all pass
    res = compare_reports(base, rep(row(500.0, 0.60, us=2000.0)),
                          normalize=True)
    assert res.n_regressions == 0
    assert {v.verdict for v in res.verdicts} == {"pass"}
