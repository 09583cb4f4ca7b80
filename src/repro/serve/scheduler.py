"""Slot-level continuous batching and the legacy static-cohort scheduler.

``ContinuousScheduler`` keeps a fixed number of batch *slots* decoding in
one jitted step over a shared :class:`~repro.serve.cache.PagedKVCache`
arena.  A finished slot is freed and refilled from the arrival queue on
the very next step, so short requests never hold the batch hostage the
way cohort scheduling does — occupancy stays near 1 under mixed-length
traffic, which is where the tokens/s win comes from.

One loop serves both prefill policies.  Each iteration admits arrived
requests into free slots, does prefill work, then runs one decode step
over the slots past prefill.  Monolithic prefill runs each admission's
whole prompt at ``B=1`` with its *exact* token length (no left padding)
and scatters the dense cache into the slot's arena blocks; chunked
prefill (``chunk_tokens``) runs one chunk of the oldest mid-prefill slot
an iteration.  Either way every request's greedy output is bit-identical
to a one-request-at-a-time oracle of the same policy regardless of
arrival order, batch size, or what else shares the batch — the property
the serving tests pin.  The decode step compiles exactly once.

``CohortScheduler`` is the old ``ServingLoop`` body behind the same
interface: take up to ``batch`` arrived requests, left-pad, prefill,
decode the cohort in lockstep until all members finish, repeat.  It
exists as the measured baseline the ``serve/*`` bench scenarios compare
against, with two fixes over the original: the prefill sample no longer
reuses the loop's PRNG key, and the prefill KV budget is bucketed to the
next power of two to cap jit recompiles across cohorts.

Time is *virtual*: arrivals are expressed in scheduler steps (one prefill
or one batch-decode step advances the clock by 1), so a trace replays
identically on any host speed.  Wall-clock is only used for the latency
metrics themselves (TTFT, decode ms).

Both schedulers report the same ``repro.obs.metrics`` names the original
loop did:

  serve.ttft_ms           histogram, per request (arrival -> first token)
  serve.decode_ms         histogram, per decode step (per-token latency)
  serve.batch_occupancy   histogram, active/batch per decode step
                          (per cohort prefill for CohortScheduler)
  serve.requests_total    counter
  serve.tokens_total      counter

``ContinuousScheduler`` also records one span tree per scheduler
iteration on the process's tracer (``repro.obs.trace``), which records
while it is enabled or while a JAX profiler session is active.  One span
per phase, never one per slot, each a child of the iteration's:

  serve.step              the iteration, from the arrival drain to the end
                          of readback; ``active``, ``queued``,
                          ``prefilling`` as counted after the drain
  serve.admit             one admission (arena blocks, prefix match);
                          ``uid``, ``prompt_len``, ``matched``, ``waited_ms``
  serve.prefill           one monolithic prefill dispatch, or
  serve.prefill_chunk     one chunk dispatch; ``uid``, ``start``, ``n_tokens``
  serve.decode_step       the decode dispatch (inputs, block-table upload,
                          the jitted call); ``active``
  serve.device_wait       each wait for the device's tokens; ``on`` is
                          ``prefill``, ``chunk`` or ``decode``
  serve.sample            sampling at temperature > 0; ``active``
  serve.readback          tokens to the host, appends, finished requests
                          freed; ``n_tokens``, ``finished``, ``transfers``
                          (device-to-host copies made in it: one per
                          dispatch, whatever the batch)

A dispatch's tokens reach the host in one copy: the scheduler starts it
with ``copy_to_host_async`` as soon as the dispatch is made, waits for
the device, then converts the whole array once.  No device array is
indexed per slot (a ``jax.Array`` index is a dispatch and a sync of its
own).

For a model with experts, each decode step and prefill chunk also returns
how many of its tokens each expert layer routed to each expert.  While the
tracer records, ``ContinuousScheduler`` counts those of the recorded
dispatches into its registry, once a later wait has seen the device finish
them (so reading them waits for nothing):

  moe.assignments         counter, every top-k pick of a real token
  moe.assignments_here    counter, the picks of experts held here
  moe.expert_rows         counter, rows the held experts computed,
                          padding included (``moe.capacity`` per expert)
  moe.max_expert_load     histogram, per expert layer and dispatch, the
                          most picks any held expert got
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import ArchConfig
from ..distributed import sharding as shd
from ..models import build_model
from ..models.moe import capacity, held_experts
from ..obs import metrics as obs_metrics
from ..obs.trace import get_tracer
from .cache import PagedKVCache, next_pow2, scatter_prefill

__all__ = ["Request", "sample", "pack_prompts", "mask_padded_cache",
           "build_serve_fns", "ContinuousScheduler", "CohortScheduler"]


@dataclass
class Request:
    uid: int
    prompt: np.ndarray
    max_new: int
    arrival: float = 0.0                # virtual-step arrival time
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    # filled in by the scheduler ----------------------------------------------
    ttft_ms: Optional[float] = None     # arrival -> first token (incl.
    #                                     queue wait)
    total_ms: Optional[float] = None    # arrival -> request finished
    #: a list to opt in: ``ContinuousScheduler`` then appends the logits
    #: row (a device array) each output token was chosen from
    logits: Optional[list] = None


def sample(logits, key, temperature: float = 0.0):
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def pack_prompts(active: List[Request], batch: int):
    """LEFT-pad ragged prompts into one (batch, max_len) int32 array.
    Returns (tokens, pads) where ``pads[i]`` is request i's pad count."""
    max_len = max(len(r.prompt) for r in active)
    tokens = np.zeros((batch, max_len), np.int32)
    pads = np.zeros((batch,), np.int32)
    for i, r in enumerate(active):
        p = np.asarray(r.prompt, np.int32).reshape(-1)
        pads[i] = max_len - len(p)
        tokens[i, pads[i]:] = p
    return tokens, pads


def mask_padded_cache(state, pads: np.ndarray):
    """Rewrite the pad slots' cached positions to -1 so ``attend_decode``
    (which masks ``pos_cache < 0`` as empty) never attends them."""
    kpos = getattr(state, "kpos", None)
    if kpos is None or not np.any(pads):
        return state
    slot = jnp.arange(kpos.shape[-1], dtype=jnp.int32)
    pad_col = jnp.asarray(pads, jnp.int32)[None, :, None]
    masked = jnp.where(slot[None, None, :] < pad_col, -1, kpos)
    return state._replace(kpos=masked)


def build_serve_fns(model, rules=None, budget=None):
    def prefill(params, batch):
        with shd.use_rules(rules):
            return model.prefill(params, batch, budget=budget)

    def decode_step(params, state, tokens):
        with shd.use_rules(rules):
            return model.decode_step(params, state, tokens)

    return jax.jit(prefill), jax.jit(decode_step, donate_argnums=(1,))


def _keep_logits(req: Request, logits, i: int) -> None:
    """Record row ``i`` of ``logits`` if ``req`` asked for its logits (the
    slice is taken only then, so the serving path pays nothing)."""
    if req.logits is not None:
        req.logits.append(logits[i])


def _since_ms(t: float) -> float:
    return (time.perf_counter() - t) * 1e3


def _occupancy(slots) -> Dict[str, int]:
    """Decoding and mid-prefill slots, as ``serve.step`` records them."""
    prefilling = sum(s is not None and s.prefilling for s in slots)
    return {"active": sum(s is not None for s in slots) - prefilling,
            "prefilling": prefilling}


def _request_key(base_key, uid: int):
    """Per-request PRNG stream: independent of scheduling order, so
    sampled outputs don't change when the batch composition does."""
    return jax.random.fold_in(base_key, uid)


class _SchedulerBase:
    """Shared construction + metrics wiring for both schedulers."""

    def __init__(self, cfg: ArchConfig, params, *, batch: int,
                 rules=None, seed: int = 0, max_new: int = 64,
                 metrics: Optional[obs_metrics.Registry] = None):
        self.cfg = cfg
        self.params = params
        self.batch = batch
        self.model = build_model(cfg)
        self.max_new = max_new
        self.rules = rules
        self.seed = seed
        self.key = jax.random.PRNGKey(seed)
        self.metrics = metrics if metrics is not None \
            else obs_metrics.Registry()

    def _metric_handles(self):
        m = self.metrics
        return (m.histogram("serve.ttft_ms"), m.histogram("serve.decode_ms"),
                m.histogram("serve.batch_occupancy"),
                m.counter("serve.requests_total"),
                m.counter("serve.tokens_total"))


class _Slot:
    """One occupied batch slot of the continuous scheduler."""

    __slots__ = ("req", "pos", "target", "t_arrive", "plen", "filled",
                 "prefilling", "started")

    def __init__(self, req: Request, plen: int, target: int,
                 t_arrive: float, filled: int):
        self.req = req
        self.plen = plen        # prompt rows this slot must prefill
        self.pos = plen         # next cache row this slot writes
        self.target = target    # tokens to emit (min(max_new, max_steps))
        self.t_arrive = t_arrive
        self.filled = filled    # prompt rows cached so far (chunk-aligned)
        self.prefilling = True  # until its first token is read back
        self.started = False    # chunked: True once the first chunk ran


class ContinuousScheduler(_SchedulerBase):
    """Slot-level continuous batching over a paged KV arena.

    ``total_tokens`` sets the arena budget (default: enough for every
    slot to hold ``max_seq`` rows); ``max_seq`` bounds one request's
    prompt + generation.

    ``chunk_tokens`` switches prefill from one monolithic exact-length
    dispatch to fixed-size chunks interleaved with decode steps (one
    chunk, then one decode step, per scheduler step), bounding how long
    a queued long prompt can stall decoders.  Chunk boundaries are
    *absolute* row multiples of ``chunk_tokens`` and every chunk runs the
    same full-softmax dispatch shape, so greedy outputs stay bit-identical
    to a chunked solo oracle regardless of arrival order, batch mix, or
    prefix sharing (they are NOT bit-comparable to the monolithic path,
    whose online-softmax decomposition differs in low bits).
    ``prefix_cache`` additionally content-addresses finished full blocks
    and admits new prompts by mapping their longest cached prefix —
    implies chunked prefill (default ``block_len`` — the finest legal
    chunk, so as much of a shared prefix as possible lands on a match
    boundary) because shared rows must end on an absolute chunk
    boundary."""

    def __init__(self, cfg: ArchConfig, params, *, batch: int,
                 rules=None, seed: int = 0, max_new: int = 64,
                 metrics: Optional[obs_metrics.Registry] = None,
                 block_len: int = 16, max_seq: int = 1024,
                 total_tokens: Optional[int] = None,
                 chunk_tokens: Optional[int] = None,
                 prefix_cache: bool = False):
        super().__init__(cfg, params, batch=batch, rules=rules, seed=seed,
                         max_new=max_new, metrics=metrics)
        if self.model.decode_paged is None:
            raise ValueError(
                f"family {cfg.family!r} has no paged decode path; use "
                "CohortScheduler")
        self.block_len = block_len
        self.max_seq = max_seq
        if prefix_cache and chunk_tokens is None:
            # finest legal chunk: match length is capped to chunk
            # multiples, so coarser defaults silently shrink sharing
            chunk_tokens = block_len
        if chunk_tokens is not None:
            if chunk_tokens < block_len or chunk_tokens % block_len:
                raise ValueError(
                    f"chunk_tokens {chunk_tokens} must be a positive "
                    f"multiple of block_len {block_len}")
            if int(cfg.n_patches or 0) > 0:
                raise ValueError(
                    "chunked prefill does not support vlm prompts (patch "
                    "rows cannot be chunk-aligned); use the monolithic "
                    "path")
        self.chunk_tokens = chunk_tokens
        self.prefix_cache = bool(prefix_cache)
        if total_tokens is None:
            total_tokens = batch * max_seq
        self.cache = PagedKVCache(cfg, batch, total_tokens=total_tokens,
                                  max_seq=max_seq, block_len=block_len,
                                  prefix_cache=self.prefix_cache)
        self._prefill_fns = {}          # KV bucket -> jitted prefill
        self._chunk_fns = {}            # pow2 chunk width -> jitted chunk
        # vlm prompts prepend n_patches rows to the cache during prefill
        self._extra_rows = int(cfg.n_patches or 0)

        model, rules_ = self.model, self.rules
        # (routing counts, rows) of recorded dispatches not yet counted
        self._picks: List = []

        def _decode(params, paged, tokens, tables, slot_pos):
            with shd.use_rules(rules_):
                logits, paged = model.decode_paged(params, paged, tokens,
                                                   tables, slot_pos)
            # fold the greedy pick into the same dispatch: one jit call
            # per decode step instead of decode + eager argmax
            return (logits, jnp.argmax(logits, axis=-1),
                    paged._replace(picks=None), paged.picks)

        self._decode = jax.jit(_decode, donate_argnums=(1,))

    # -- helpers ------------------------------------------------------------

    def _bucket(self, prompt_len: int) -> int:
        """KV budget for one prefill: next power of two, whole blocks."""
        b = max(next_pow2(max(prompt_len, 1)), self.block_len)
        bl = self.block_len
        return -(-b // bl) * bl

    def _get_prefill(self, bucket: int):
        """Fused prefill -> scatter-into-blocks -> greedy pick, one jitted
        dispatch per admission (donating the arena)."""
        fn = self._prefill_fns.get(bucket)
        if fn is None:
            model, rules = self.model, self.rules

            def prefill_write(params, batch, paged, ids):
                with shd.use_rules(rules):
                    logits, dense = model.prefill(params, batch,
                                                  budget=bucket)
                paged = scatter_prefill(paged, dense.k, dense.v,
                                        dense.kpos[0, 0], ids)
                return logits, jnp.argmax(logits, axis=-1), paged

            fn = self._prefill_fns[bucket] = jax.jit(
                prefill_write, donate_argnums=(2,))
        return fn

    def _get_chunk(self, width: int):
        """Jitted single-slot prefill chunk at pow2 ``width`` (compiles
        once per width: at most log2(next_pow2(chunk_tokens)) + 1 entries
        across any trace — the jit-cache-boundedness tests pin this)."""
        fn = self._chunk_fns.get(width)
        if fn is None:
            model, rules = self.model, self.rules

            def chunk_step(params, paged, tokens, table, start, n_real):
                with shd.use_rules(rules):
                    logits, paged = model.prefill_chunk(
                        params, paged, tokens, table, start, n_real)
                return (logits, jnp.argmax(logits, axis=-1),
                        paged._replace(picks=None), paged.picks)

            fn = self._chunk_fns[width] = jax.jit(chunk_step,
                                                  donate_argnums=(1,))
        return fn

    def _prefill_batch(self, prompt: np.ndarray):
        batch = {"tokens": jnp.asarray(
            np.asarray(prompt, np.int32).reshape(1, -1))}
        if self.cfg.n_patches:
            batch["patches"] = jnp.zeros(
                (1, self.cfg.n_patches, self.cfg.d_model), jnp.float32)
        return batch

    # -- routing counters ---------------------------------------------------

    def _note_picks(self, picks, rows: int, span) -> None:
        """Keep a dispatch's routing counts if its span was recorded."""
        if span is not None and picks is not None:
            self._picks.append((picks, rows))

    def _count_picks(self) -> None:
        """Count the kept routing counts into the registry.  Called after
        a wait on a later dispatch, so the device has finished them."""
        if not self._picks:
            return
        m = self.metrics
        lo, n = held_experts(self.cfg)
        load = m.histogram("moe.max_expert_load")
        for picks, rows in self._picks:
            c = np.asarray(picks)           # (expert layers, experts)
            here = c[:, lo:lo + n]
            m.counter("moe.assignments").inc(int(c.sum()))
            m.counter("moe.assignments_here").inc(int(here.sum()))
            m.counter("moe.expert_rows").inc(
                c.shape[0] * n * capacity(self.cfg, rows))
            for v in here.max(axis=1):
                load.observe(int(v))
        self._picks.clear()

    # -- main loop ----------------------------------------------------------

    def _lifetime(self, plen: int, target: int) -> int:
        """Rows to reserve for a request: prompt plus output, and at least
        the monolithic prefill's bucket, which it writes whole."""
        if self.chunk_tokens is None:
            return max(self._bucket(plen), plen + target)
        return plen + target

    def _admit(self, run: "_Run", i: int, r: Request, plen: int,
               target: int, lifetime: int) -> _Slot:
        """Give slot ``i`` to ``r``: reserve its lifetime's arena blocks,
        allocating the prefill bucket's now (monolithic) or mapping the
        longest cached prefix (chunked).  The match is capped to whole
        chunks strictly below the prompt's last row, so at least one chunk
        (and the first token's logits) is always computed live with the
        same dispatch shape the solo oracle uses."""
        T = self.chunk_tokens
        with get_tracer().span("serve.admit", uid=r.uid,
                               prompt_len=len(r.prompt),
                               waited_ms=_since_ms(run.arrive_wall[r.uid])
                               ) as adm:
            if T is None:
                self.cache.admit(i, self._bucket(plen), lifetime)
                matched = 0
            else:
                matched = self.cache.admit_shared(
                    i, np.asarray(r.prompt, np.int32).reshape(-1), lifetime,
                    max_match_rows=((plen - 1) // T) * T, granule_rows=T)
            if adm is not None:
                adm.attrs["matched"] = matched
        if T is not None:
            run.hit_c.inc(matched)
            run.miss_c.inc(plen - matched)
        return _Slot(r, plen, target, run.arrive_wall[r.uid], matched)

    def _prefill(self, run: "_Run", i: int) -> None:
        """One prefill dispatch for slot ``i``: its whole prompt, or its
        next chunk.  Once the prompt is cached its first token is read
        back.  Either way the virtual clock then advances one step and the
        arrivals it makes due join the queue."""
        s = run.slots[i]
        r = s.req
        if self.chunk_tokens is None:
            bucket = self._bucket(s.plen)
            with get_tracer().span("serve.prefill", uid=r.uid, start=0,
                                   n_tokens=len(r.prompt)):
                logits, greedy, self.cache.state = self._get_prefill(bucket)(
                    self.params, self._prefill_batch(r.prompt),
                    self.cache.state,
                    jnp.asarray(self.cache.tables[i, :bucket
                                                  // self.block_len]))
                s.filled = s.plen
                if run.temperature <= 0:
                    greedy.copy_to_host_async()
        else:
            logits, greedy = self._prefill_chunk(run, i)
        if s.filled >= s.plen:
            self._first_token(run, i, logits, greedy)
        run.clock += 1.0
        run.drain_arrivals()

    def _prefill_chunk(self, run: "_Run", i: int):
        """Dispatch slot ``i``'s next prefill chunk; returns its logits and
        greedy pick."""
        s = run.slots[i]
        T = self.chunk_tokens
        prompt = np.asarray(s.req.prompt, np.int32).reshape(-1)
        with get_tracer().span("serve.prefill_chunk", uid=s.req.uid) as pc:
            if not s.started:
                # last chance to share: a producer that was still
                # mid-prefill at our admission has registered its completed
                # chunks by now — graft them on while this slot has written
                # nothing
                grown = self.cache.extend_match(
                    i, prompt, max_match_rows=((s.plen - 1) // T) * T,
                    granule_rows=T)
                if grown > s.filled:
                    run.hit_c.inc(grown - s.filled)
                    run.miss_c.inc(s.filled - grown)
                    s.filled = grown
                s.started = True
            start = s.filled
            n = min(T, s.plen - start)
            if pc is not None:
                pc.attrs.update(start=start, n_tokens=n)
            width = next_pow2(n)
            self.cache.extend_to(i, start + n)
            toks = np.zeros((1, width), np.int32)
            toks[0, :n] = prompt[start:start + n]
            logits, greedy, self.cache.state, picks = self._get_chunk(width)(
                self.params, self.cache.state, jnp.asarray(toks),
                jnp.asarray(self.cache.tables[i:i + 1]),
                jnp.int32(start), jnp.int32(n))
            self._note_picks(picks, width, pc)
            s.filled = start + n
            if s.filled >= s.plen and run.temperature <= 0:
                greedy.copy_to_host_async()
            # register incrementally: rows in completed absolute chunks are
            # final (later chunks never rewrite them), so a prompt arriving
            # mid-prefill can already share them.  Only FULL aligned chunks
            # qualify — rows of a final partial chunk ran at a different
            # dispatch width, so their low bits are not what a sharing
            # consumer's oracle would produce.
            self.cache.register_prefix(i, prompt, (s.filled // T) * T)
        return logits, greedy

    def _first_token(self, run: "_Run", i: int, logits, greedy) -> None:
        """Wait for slot ``i``'s first token, read it back and start the
        slot decoding (or finish it, if that was its last token)."""
        tracer = get_tracer()
        s = run.slots[i]
        r = s.req
        if run.temperature > 0:
            with tracer.span("serve.sample", active=1):
                greedy = sample(logits, jax.random.fold_in(
                    _request_key(run.base_key, r.uid), 0), run.temperature)
        with tracer.span("serve.device_wait", on="prefill"
                         if self.chunk_tokens is None else "chunk"):
            first = jax.block_until_ready(greedy)
        self._count_picks()
        with tracer.span("serve.readback", n_tokens=1, transfers=1) as rb:
            tok = int(np.asarray(first)[0])
            r.ttft_ms = _since_ms(s.t_arrive)
            run.ttft_h.observe(r.ttft_ms)
            r.out_tokens.append(tok)
            _keep_logits(r, logits, 0)
            s.prefilling = False
            done = len(r.out_tokens) >= s.target
            if done:
                self._finish(run, i)
            if rb is not None:
                rb.attrs["finished"] = int(done)

    def _finish(self, run: "_Run", i: int) -> None:
        """Slot ``i``'s request is done: record it and free the slot."""
        s = run.slots[i]
        s.req.done = True
        s.req.total_ms = _since_ms(s.t_arrive)
        run.results[s.req.uid] = s.req.out_tokens
        run.req_c.inc()
        run.tok_c.inc(len(s.req.out_tokens))
        self.cache.free_slot(i)
        run.slots[i] = None

    def _decode_and_read(self, run: "_Run", active: List[int]) -> None:
        """One decode step over the ``active`` slots, then their tokens to
        the host.  Mid-prefill slots are masked out of the block table:
        their decode rows are inactive (slot_pos 0) and must write the
        scratch block, not the real (possibly shared) block their table
        maps at row 0."""
        tracer = get_tracer()
        slots, temperature = run.slots, run.temperature
        run.occ_h.observe(len(active) / self.batch)
        with tracer.span("serve.decode_step", active=len(active)) as ds:
            tokens = np.zeros((self.batch, 1), np.int32)
            slot_pos = np.zeros((self.batch,), np.int32)
            for i in active:
                s = slots[i]
                tokens[i, 0] = s.req.out_tokens[-1]
                slot_pos[i] = s.pos
                self.cache.append(i, s.pos)
            t0 = time.perf_counter()
            pref = [i for i, s in enumerate(slots)
                    if s is not None and s.prefilling]
            if pref:
                tbl = self.cache.tables.copy()
                tbl[pref] = -1
                tables = jnp.asarray(tbl)
            else:
                tables = self.cache.device_tables()
            logits, greedy, self.cache.state, picks = self._decode(
                self.params, self.cache.state, jnp.asarray(tokens),
                tables, jnp.asarray(slot_pos))
            if temperature <= 0:
                # runs as soon as the step ends: the readback then finds
                # the tokens already on the host
                greedy.copy_to_host_async()
            self._note_picks(picks, self.batch, ds)
        with tracer.span("serve.device_wait", on="decode"):
            toks = jax.block_until_ready(greedy if temperature <= 0
                                         else logits)
        self._count_picks()
        if temperature > 0:
            with tracer.span("serve.sample", active=len(active)):
                toks = np.zeros((self.batch,), np.int64)
                for i in active:
                    s = slots[i]
                    step_key = jax.random.fold_in(
                        _request_key(run.base_key, s.req.uid),
                        len(s.req.out_tokens))
                    toks[i] = int(jax.block_until_ready(sample(
                        logits[i:i + 1], step_key, temperature))[0])
        run.dec_h.observe((time.perf_counter() - t0) * 1e3)
        with tracer.span("serve.readback", n_tokens=len(active),
                         transfers=int(temperature <= 0)) as rb:
            host = np.asarray(toks).tolist()
            finished = 0
            for i in active:
                s = slots[i]
                s.pos += 1
                s.req.out_tokens.append(host[i])
                _keep_logits(s.req, logits, i)
                if len(s.req.out_tokens) >= s.target:
                    self._finish(run, i)
                    finished += 1
            if rb is not None:
                rb.attrs["finished"] = finished

    def run(self, requests: List[Request], temperature: float = 0.0,
            max_steps: int = 64) -> Dict[int, List[int]]:
        """Serve ``requests``; returns each one's output tokens by uid.
        Each iteration admits arrived requests into free slots, does the
        prefill work (monolithic: every admission's whole prompt; chunked:
        one chunk for the oldest mid-prefill slot), then one decode step
        over the slots past prefill."""
        tracer = get_tracer()
        run = _Run(self, requests, temperature)
        slots, queue, pending = run.slots, run.queue, run.pending
        chunked = self.chunk_tokens is not None
        while pending or queue or any(s is not None for s in slots):
            with tracer.span("serve.step") as step:
                run.drain_arrivals()
                if step is not None:
                    step.attrs.update(_occupancy(slots), queued=len(queue))

                # admission: refill free slots while the arena has room
                n_adm = 0
                while queue and n_adm < self.batch:
                    free = [i for i, s in enumerate(slots) if s is None]
                    if not free:
                        break
                    r = queue[0]
                    target = min(r.max_new, max_steps)
                    plen = len(r.prompt) + self._extra_rows
                    lifetime = self._lifetime(plen, target)
                    if not self.cache.can_admit(lifetime):
                        if not any(s is not None for s in slots):
                            raise RuntimeError(
                                f"request {r.uid} (lifetime {lifetime} "
                                f"tokens) cannot fit the arena even when "
                                f"idle")
                        break           # wait for a slot to free blocks
                    queue.popleft()
                    i = free[0]
                    slots[i] = self._admit(run, i, r, plen, target, lifetime)
                    n_adm += 1
                    if not chunked:
                        self._prefill(run, i)

                if chunked:
                    pref = [i for i, s in enumerate(slots)
                            if s is not None and s.prefilling]
                    if pref:
                        self._prefill(run, min(pref, key=lambda j: (
                            slots[j].req.arrival, slots[j].req.uid)))

                active = [i for i, s in enumerate(slots)
                          if s is not None and not s.prefilling]
                if not active:
                    if any(s is not None for s in slots):
                        continue        # prefill chunks still in flight
                    if pending:
                        # idle: jump the virtual clock to the next arrival
                        run.clock = max(run.clock, pending[0].arrival)
                        continue
                    if queue:
                        continue        # admission will retry (or raise)
                    break

                # one decode step over every slot (inactive slots write the
                # scratch block and their logits are discarded)
                self._decode_and_read(run, active)
                run.clock += 1.0
        return run.results


class _Run:
    """The state of one ``ContinuousScheduler.run``: requests not yet due,
    arrived ones waiting for a slot, the slots, the finished outputs, the
    virtual clock and the registry's handles."""

    def __init__(self, sched: ContinuousScheduler, requests: List[Request],
                 temperature: float):
        self.pending = deque(sorted(requests, key=lambda r: (r.arrival,
                                                             r.uid)))
        self.queue: deque = deque()     # arrived, waiting for a slot
        self.arrive_wall: Dict[int, float] = {}
        self.slots: List[Optional[_Slot]] = [None] * sched.batch
        self.results: Dict[int, List[int]] = {}
        self.clock = 0.0                # virtual steps
        self.temperature = temperature
        self.base_key = jax.random.PRNGKey(sched.seed)
        (self.ttft_h, self.dec_h, self.occ_h, self.req_c,
         self.tok_c) = sched._metric_handles()
        if sched.chunk_tokens is not None:
            self.hit_c = sched.metrics.counter("serve.prefix_hit_tokens")
            self.miss_c = sched.metrics.counter("serve.prefix_miss_tokens")

    def drain_arrivals(self) -> None:
        """Queue the requests the virtual clock has reached."""
        now = time.perf_counter()
        while self.pending and self.pending[0].arrival <= self.clock:
            r = self.pending.popleft()
            self.queue.append(r)
            self.arrive_wall[r.uid] = now


class CohortScheduler(_SchedulerBase):
    """Static-cohort serving: up to ``batch`` arrived requests prefill
    together, decode in lockstep until every member finishes, then the
    next cohort forms.  The measured baseline for continuous batching."""

    def __init__(self, cfg: ArchConfig, params, *, batch: int,
                 rules=None, seed: int = 0, max_new: int = 64,
                 metrics: Optional[obs_metrics.Registry] = None):
        super().__init__(cfg, params, batch=batch, rules=rules, seed=seed,
                         max_new=max_new, metrics=metrics)
        self._fns = {}          # KV budget bucket -> (prefill, decode)

    def _get_fns(self, prompt_len: int):
        # power-of-two budget bucketing: cohorts whose budgets round to
        # the same bucket share one decode compilation instead of
        # recompiling per distinct (prompt_len + max_new)
        budget = next_pow2(prompt_len + self.max_new + 1)
        if budget not in self._fns:
            self._fns[budget] = build_serve_fns(self.model, self.rules,
                                                budget=budget)
        return self._fns[budget]

    def run(self, requests: List[Request], temperature: float = 0.0,
            max_steps: int = 64) -> Dict[int, List[int]]:
        tracer = get_tracer()
        ttft_h, dec_h, occ_h, req_c, tok_c = self._metric_handles()

        pending = deque(sorted(requests, key=lambda r: (r.arrival, r.uid)))
        queue: deque = deque()
        arrive_wall: Dict[int, float] = {}
        results: Dict[int, List[int]] = {}
        clock = 0.0

        while pending or queue:
            now = time.perf_counter()
            while pending and pending[0].arrival <= clock:
                r = pending.popleft()
                queue.append(r)
                arrive_wall[r.uid] = now
            if not queue:               # idle until the next arrival
                clock = max(clock, pending[0].arrival)
                continue
            active = [queue.popleft()
                      for _ in range(min(self.batch, len(queue)))]
            occ_h.observe(len(active) / self.batch)
            with tracer.span("serve.batch", n_active=len(active),
                             queued=len(queue)):
                prompts, pads = pack_prompts(active, self.batch)
                prefill_fn, decode_fn = self._get_fns(prompts.shape[1])
                batch = {"tokens": jnp.asarray(prompts)}
                if self.cfg.is_encdec:
                    batch["frames"] = jnp.zeros(
                        (self.batch, prompts.shape[1], self.cfg.d_model),
                        jnp.float32)
                if self.cfg.n_patches:
                    batch["patches"] = jnp.zeros(
                        (self.batch, self.cfg.n_patches, self.cfg.d_model),
                        jnp.float32)
                with tracer.span("serve.prefill",
                                 prompt_len=int(prompts.shape[1])):
                    logits, state = prefill_fn(self.params, batch)
                    state = mask_padded_cache(state, pads)
                    # split before sampling: the loop key must never be
                    # consumed directly, or the next split replays it
                    self.key, sub = jax.random.split(self.key)
                    toks = sample(logits, sub, temperature)[:, None]
                    toks = jax.block_until_ready(toks)
                clock += 1.0
                t_first = time.perf_counter()
                for r in active:
                    r.ttft_ms = (t_first - arrive_wall[r.uid]) * 1e3
                    ttft_h.observe(r.ttft_ms)
                for step in range(max_steps):
                    host = np.asarray(toks)[:, 0].tolist()  # one copy a step
                    for i, r in enumerate(active):
                        if not r.done and len(r.out_tokens) < r.max_new:
                            r.out_tokens.append(host[i])
                        elif not r.done:
                            r.done = True
                    if all(r.done or len(r.out_tokens) >= r.max_new
                           for r in active):
                        break
                    self.key, sub = jax.random.split(self.key)
                    t0 = time.perf_counter()
                    with tracer.span("serve.decode_step", step=step):
                        logits, state = decode_fn(self.params, state,
                                                  toks.astype(jnp.int32))
                        toks = sample(logits, sub, temperature)[:, None]
                        toks = jax.block_until_ready(toks)
                    dec_h.observe((time.perf_counter() - t0) * 1e3)
                    clock += 1.0
                t_done = time.perf_counter()
                for r in active:
                    r.total_ms = (t_done - arrive_wall[r.uid]) * 1e3
                    results[r.uid] = r.out_tokens
                    req_c.inc()
                    tok_c.inc(len(r.out_tokens))
        return results
