"""The paper's asynchronous-copy patterns as composable Pallas TPU emitters.

This is the core contribution adapted to TPU: the A100 ``cp.async``
(global -> shared memory, register-bypassing, overlappable with compute)
becomes the TPU async DMA (HBM -> VMEM via ``pltpu.make_async_copy`` + DMA
semaphores).  The paper's Algorithms 1-3 map to four selectable strategies:

  Strategy.SYNC            GPU baseline: copy, wait, *stage through a second
                           VMEM buffer* (models the register round-trip),
                           compute.  DMA engine idle during compute.
  Strategy.REGISTER_BYPASS Alg. 1: copy, wait, compute directly on the DMA
                           landing buffer.  No overlap, no staging copy.
  Strategy.OVERLAP         Alg. 2: k-slot ring buffer, up to ``wait_group``
                           copies in flight while tile i computes; wait
                           placed *before* compute (the paper's
                           block-synchronization point).
  Strategy.DROP_OFF        Alg. 3: sub-tile chunks; wait for chunk c, read it
                           into VREG values, issue the next DMA *before*
                           computing on c.  No tile-level barrier.
  Strategy.TMA             Hopper-style bulk copies (Luo et al.,
                           arXiv:2402.13499 / 2501.12084): one descriptor-
                           issued 1D/2D bulk copy per operand tile, all
                           operands of a tile completing on one shared
                           per-slot barrier semaphore (the mbarrier
                           arrive/expect-tx analogue) instead of per-copy
                           wait groups.  The consumer posts a single
                           grouped wait per tile and computes directly in
                           the landing buffer; the ring always runs at its
                           deepest issue-ahead (``depth - 1``) because the
                           mbarrier decouples producer issue from consumer
                           waits — ``wait_group`` does not apply.

The pipeline *shape* is a first-class value, ``PipelineSpec``:

  ``depth``       VMEM ring-buffer slots (N-stage pipeline, not just double
                  buffering)
  ``wait_group``  how many copies may still be in flight when compute on
                  tile i begins — the TPU analogue of ``cp.async.wait_group
                  N``.  ``None`` means the deepest safe value, ``depth - 1``.
  ``out_depth``   write-back ring slots for the ``WriteBack`` drain

Kernels receive a ``TileStream`` per HBM operand and drive it through one of
the ``emit_*`` loop builders below (normally via ``emit(spec, ...)``), or
hand-roll the pattern when their data flow does not fit (wavefront kernels).
Everything here works identically in ``interpret=True`` mode on CPU, which
is how tests validate the kernels.
"""
from __future__ import annotations

import enum
import functools
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hardware import VMEM_BYTES


#: Mosaic's default scoped-VMEM budget per kernel on v5e.  A kernel whose
#: scratch needs more must raise ``vmem_limit_bytes`` or it fails to compile.
DEFAULT_SCOPED_VMEM = 16 * 2 ** 20
#: lanes in a vreg: the minor-dim tile every unmasked slice and DMA of a
#: 32-bit array must be aligned to
LANES = 128


def lane_pad(n: int) -> int:
    """``n`` rounded up to a whole number of lanes."""
    return -(-n // LANES) * LANES


def vmem_bytes(scratch_shapes: Sequence[Any]) -> int:
    """Bytes of VMEM the ``pltpu.VMEM`` entries of ``scratch_shapes`` take
    (semaphores are not VMEM and count zero)."""
    total = 0
    for s in scratch_shapes:
        if getattr(s, "memory_space", None) == pltpu.VMEM:
            n = 1
            for d in s.shape:
                n *= d
            total += n * jnp.dtype(s.dtype).itemsize
    return total


def compiler_params(*, dimension_semantics: Tuple[str, ...],
                    scratch_shapes: Sequence[Any] = ()):
    """Mosaic compiler params for a kernel with ``scratch_shapes``.  When
    the scratch takes more than half the default scoped VMEM (the rest
    holds Mosaic's own temporaries, such as tiles loaded as values), the
    limit is raised to the scratch plus one default budget of headroom;
    scratch beyond the chip's VMEM is refused here."""
    need = vmem_bytes(scratch_shapes)
    if need > VMEM_BYTES:
        raise ValueError(f"kernel scratch needs {need} bytes of VMEM, more "
                         f"than the {VMEM_BYTES} the chip has; use "
                         "smaller tiles")
    limit = None
    if need > DEFAULT_SCOPED_VMEM // 2:
        limit = min(need + DEFAULT_SCOPED_VMEM, VMEM_BYTES)
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics,
                                vmem_limit_bytes=limit)


class Strategy(enum.Enum):
    SYNC = "sync"
    REGISTER_BYPASS = "register_bypass"
    OVERLAP = "overlap"
    DROP_OFF = "drop_off"
    TMA = "tma"


ALL_STRATEGIES: Tuple[Strategy, ...] = tuple(Strategy)


def parse_strategy(name: Union[str, Strategy]) -> Strategy:
    """Parse a strategy name, case-insensitively; the error names the valid
    choices so a CLI ``--strategy`` typo is self-explaining."""
    if isinstance(name, Strategy):
        return name
    try:
        return Strategy(str(name).strip().lower())
    except ValueError:
        valid = ", ".join(s.value for s in Strategy)
        raise ValueError(
            f"unknown strategy {name!r}; valid strategies: {valid}") from None


_SINGLE_BUFFERED = (Strategy.SYNC, Strategy.REGISTER_BYPASS)


@dataclass(frozen=True)
class PipelineSpec:
    """The shape of one kernel's async pipeline — strategy, input ring depth,
    wait-group depth, and output (write-back) ring depth.

    Frozen and hashable so it can travel through jit static arguments.
    ``wait_group`` caps how many input copies may remain in flight when the
    wait for tile i is posted (``cp.async.wait_group N`` on A100): the
    emitters issue tile ``i + A`` before waiting tile ``i`` where
    ``A = min(wait_group, depth - 1)``; ``wait_group=None`` means the
    deepest safe issue-ahead, ``depth - 1``.
    """
    strategy: Strategy = Strategy.OVERLAP
    depth: int = 2
    wait_group: Optional[int] = None
    out_depth: int = 2

    def __post_init__(self):
        # accept strategy names ("overlap") anywhere a spec is built —
        # wrappers and tuned configs carry strings through jit static args
        object.__setattr__(self, "strategy", parse_strategy(self.strategy))
        if self.depth < 1:
            raise ValueError(f"depth must be >= 1, got {self.depth}")
        if self.wait_group is not None and self.wait_group < 0:
            raise ValueError(
                f"wait_group must be >= 0 or None, got {self.wait_group}")
        if self.out_depth < 1:
            raise ValueError(f"out_depth must be >= 1, got {self.out_depth}")

    @property
    def ring_depth(self) -> int:
        """Input VMEM ring slots actually allocated: single-buffered
        strategies take one slot; async strategies at least two."""
        return 1 if self.strategy in _SINGLE_BUFFERED else max(self.depth, 2)

    @property
    def ahead(self) -> int:
        """Issue-ahead distance A: tile i+A is started before tile i's wait.
        Equivalently, at most A copies are in flight during compute on i.
        TMA always runs at the deepest issue-ahead: its mbarrier counts
        transaction arrivals per slot, so there is no wait-group axis."""
        if self.strategy in _SINGLE_BUFFERED:
            return 0
        limit = self.ring_depth - 1
        if self.strategy is Strategy.TMA:
            return limit
        return limit if self.wait_group is None \
            else max(0, min(self.wait_group, limit))

    @classmethod
    def from_config(cls, config: dict) -> "PipelineSpec":
        """Build a spec from a flat kernel-config dict (KERNEL_DEFAULTS /
        tuning-registry style); unrelated keys are ignored."""
        wg = config.get("wait_group")
        return cls(strategy=parse_strategy(
                       config.get("strategy", Strategy.OVERLAP)),
                   depth=int(config.get("depth", 2)),
                   wait_group=None if wg is None else int(wg),
                   out_depth=int(config.get("out_depth", 2)))


def as_spec(spec: Union[PipelineSpec, Strategy], *, depth: int = 2,
            wait_group: Optional[int] = None,
            out_depth: int = 2) -> PipelineSpec:
    """Coerce a bare Strategy (legacy call style) into a PipelineSpec."""
    if isinstance(spec, PipelineSpec):
        return spec
    return PipelineSpec(strategy=spec, depth=depth, wait_group=wait_group,
                        out_depth=out_depth)


@dataclass
class TileStream:
    """Binds one HBM operand to a VMEM ring buffer + DMA semaphores.

    ``hbm``      HBM ref (BlockSpec memory_space=pl.ANY)
    ``vmem``     VMEM scratch shaped (depth, *tile_shape)
    ``sem``      DMA semaphore array shaped (depth,)
    ``index``    tile_index -> tuple of pl.ds()/slices into ``hbm``
    """
    hbm: Any
    vmem: Any
    sem: Any
    index: Callable[[Any], Tuple]
    depth: int

    def copy(self, i, slot):
        return pltpu.make_async_copy(
            self.hbm.at[self.index(i)], self.vmem.at[slot], self.sem.at[slot])

    def start(self, i, slot):
        self.copy(i, slot).start()

    def wait(self, i, slot):
        self.copy(i, slot).wait()


def _slot(i, depth: int):
    return jax.lax.rem(i, depth) if depth > 1 else 0


def _when(cond):
    """pl.when that also accepts static python bools (n_tiles may be traced)."""
    if isinstance(cond, bool):
        def deco(f):
            return f() if cond else None
        return deco
    return pl.when(cond)


def _issue_ahead(depth: int, wait_group: Optional[int]) -> int:
    limit = depth - 1
    return limit if wait_group is None else max(0, min(wait_group, limit))


def _warm_idx(j: int, n_tiles):
    """Warm-up tile index that is safe to *trace* when ``n_tiles`` is traced.

    With a static ``n_tiles`` the ``_when`` guard skips tracing entirely, so
    the static ``j`` is known in-bounds.  With a traced ``n_tiles`` the
    guarded branch still traces, and a static ``j`` past the HBM extent
    would fail Pallas's static slice validation — clamping through the
    traced bound makes the slice dynamic (runtime execution is already
    prevented by the guard)."""
    if isinstance(n_tiles, int):
        return j
    return jnp.minimum(j, n_tiles - 1)


# ---------------------------------------------------------------------------
# Loop emitters.  ``compute(i, bufs)`` receives the tile index and one VMEM
# ref per stream and must write its own outputs (to an output stream's VMEM
# or directly to an output HBM ref via a write-back TileStream).
# ---------------------------------------------------------------------------

def emit_sync(streams: Sequence[TileStream], n_tiles: int,
              compute: Callable, *, staging: Optional[Sequence[Any]] = None):
    """Paper baseline.  Single-buffered; if ``staging`` VMEM refs are given,
    each tile is copied VMEM->VMEM first (the register-round-trip model)."""
    def body(i, _):
        for s in streams:
            s.start(i, 0)
        for s in streams:
            s.wait(i, 0)
        if staging is not None:
            for s, stage in zip(streams, staging):
                stage[...] = s.vmem[0]
            compute(i, [stage for stage in staging])
        else:
            compute(i, [s.vmem.at[0] for s in streams])
        return ()
    jax.lax.fori_loop(0, n_tiles, body, ())


def emit_register_bypass(streams: Sequence[TileStream], n_tiles: int,
                         compute: Callable):
    """Alg. 1: async copy direct to VMEM, immediate wait, compute in place."""
    emit_sync(streams, n_tiles, compute, staging=None)


def emit_overlap(streams: Sequence[TileStream], n_tiles: int,
                 compute: Callable, *, depth: int,
                 wait_group: Optional[int] = None):
    """Alg. 2: N-stage ring pipeline with grouped waits.

    Tile ``i + A`` is issued before tile ``i``'s wait, with
    ``A = min(wait_group, depth - 1)`` copies in flight during each compute
    (``wait_group=None`` -> the deepest safe ``depth - 1``).  Slot reuse is
    safe because tile ``i + A`` lands in the slot of tile ``i + A - depth``,
    whose compute finished at least one iteration ago (``A <= depth - 1``).
    """
    assert depth >= 2, "overlap needs a ring buffer of depth >= 2"
    ahead = _issue_ahead(depth, wait_group)
    # warm-up: issue the first `ahead` copies (static unroll keeps slots
    # static; guards allow a traced n_tiles)
    for j in range(ahead):
        @_when(j < n_tiles)
        def _(j=j):
            for s in streams:
                s.start(_warm_idx(j, n_tiles), j % depth)

    def body(i, _):
        slot = _slot(i, depth)
        if ahead:
            nxt = _slot(i + ahead, depth)
            @pl.when(i + ahead < n_tiles)
            def _():
                for s in streams:
                    s.start(i + ahead, nxt)
        else:                           # wait_group=0: degenerate, no overlap
            for s in streams:
                s.start(i, slot)
        for s in streams:
            s.wait(i, slot)
        compute(i, [s.vmem.at[slot] for s in streams])
        return ()
    jax.lax.fori_loop(0, n_tiles, body, ())


def emit_drop_off(streams: Sequence[TileStream], n_tiles: int,
                  compute_value: Callable, *, depth: int = 2,
                  wait_group: Optional[int] = None):
    """Alg. 3 (TPU analogue): ring-buffer at *chunk* granularity; after the
    wait, the chunk is read into VREG values and the next DMA is issued
    *before* computing.  ``compute_value(i, vals)`` receives jnp arrays (the
    "registers") and returns nothing (it writes outputs itself).  The same
    ``wait_group`` issue-ahead rule as ``emit_overlap`` applies; the
    defining difference is that the next copy is posted only after the
    current chunk has been dropped off into registers."""
    assert depth >= 2
    ahead = _issue_ahead(depth, wait_group)
    for j in range(ahead):
        @_when(j < n_tiles)
        def _(j=j):
            for s in streams:
                s.start(_warm_idx(j, n_tiles), j % depth)

    def body(i, _):
        slot = _slot(i, depth)
        if ahead == 0:
            for s in streams:
                s.start(i, slot)
        for s in streams:
            s.wait(i, slot)
        # "drop off" into registers
        vals = [s.vmem[slot] for s in streams]
        # issue the next copy before computing (no block-level barrier)
        if ahead:
            nxt = _slot(i + ahead, depth)
            @pl.when(i + ahead < n_tiles)
            def _():
                for s in streams:
                    s.start(i + ahead, nxt)
        compute_value(i, vals)
        return ()
    jax.lax.fori_loop(0, n_tiles, body, ())


def emit_tma(streams: Sequence[TileStream], n_tiles: int,
             compute: Callable, *, depth: int):
    """Hopper-TMA analogue: bulk descriptor copies completing on a shared
    per-slot barrier.

    Every operand tile moves as one 1D/2D bulk copy (the TileStream slice is
    the copy descriptor), and *all* operands of tile ``i`` signal the same
    per-slot semaphore — the mbarrier ``expect-tx`` pattern: the consumer
    posts one grouped wait of ``len(streams)`` arrivals instead of one wait
    per copy, then computes directly in the landing buffer (register-
    bypassing, like ``cp.async``, but descriptor-issued from a single
    producer).  Because the barrier decouples issue from consumption, the
    ring always runs at its deepest issue-ahead ``depth - 1``; there is no
    wait-group axis (``PipelineSpec.wait_group`` is ignored).

    ``streams[0].sem`` serves as the slot barrier array; the other streams'
    semaphores are left untouched so kernel scratch arity stays identical
    across strategies.
    """
    assert depth >= 2, "tma needs a ring buffer of depth >= 2"
    bar = streams[0].sem            # per-slot transaction barrier (mbarrier)

    def bulk_copy(s: TileStream, i, slot):
        return pltpu.make_async_copy(
            s.hbm.at[s.index(i)], s.vmem.at[slot], bar.at[slot])

    ahead = depth - 1
    for j in range(ahead):
        @_when(j < n_tiles)
        def _(j=j):
            for s in streams:
                bulk_copy(s, _warm_idx(j, n_tiles), j % depth).start()

    def body(i, _):
        slot = _slot(i, depth)
        nxt = _slot(i + ahead, depth)
        @pl.when(i + ahead < n_tiles)
        def _():
            for s in streams:
                bulk_copy(s, i + ahead, nxt).start()
        # the grouped mbarrier wait: one arrival per operand bulk copy
        for s in streams:
            bulk_copy(s, i, slot).wait()
        compute(i, [s.vmem.at[slot] for s in streams])
        return ()
    jax.lax.fori_loop(0, n_tiles, body, ())


def emit(spec: Union[PipelineSpec, Strategy], streams: Sequence[TileStream],
         n_tiles: int, compute: Callable, *, depth: int = 2,
         staging: Optional[Sequence[Any]] = None):
    """Dispatch a loop under the requested pipeline spec (or bare Strategy,
    in which case ``depth`` applies and wait_group defaults).

    ``compute(i, bufs)`` gets VMEM refs for SYNC/REGISTER_BYPASS/OVERLAP/TMA
    and jnp values for DROP_OFF (register semantics).  ``staging`` is
    consumed only by SYNC (the register-round-trip model) and may be passed
    unconditionally.
    """
    spec = as_spec(spec, depth=depth)
    if spec.strategy == Strategy.SYNC:
        emit_sync(streams, n_tiles, compute, staging=staging)
    elif spec.strategy == Strategy.REGISTER_BYPASS:
        emit_register_bypass(streams, n_tiles, compute)
    elif spec.strategy == Strategy.OVERLAP:
        emit_overlap(streams, n_tiles, compute, depth=spec.ring_depth,
                     wait_group=spec.wait_group)
    elif spec.strategy == Strategy.DROP_OFF:
        emit_drop_off(streams, n_tiles, compute, depth=spec.ring_depth,
                      wait_group=spec.wait_group)
    elif spec.strategy == Strategy.TMA:
        emit_tma(streams, n_tiles, compute, depth=spec.ring_depth)
    else:  # pragma: no cover
        raise ValueError(spec.strategy)


@dataclass
class WriteBack:
    """N-deep VMEM -> HBM result drain (the output-side Overlap).

    ``vmem`` shaped (depth, *tile_shape); ``index(i)`` gives the HBM slice
    for tile i.  ``push(i, val)`` recycles slots, waiting only when the slot's
    previous DMA is still in flight; call ``drain(n_tiles)`` after the loop
    (``n_tiles`` may be traced — the guards become ``pl.when``)."""
    hbm: Any
    vmem: Any
    sem: Any
    index: Callable[[Any], Tuple]
    depth: int = 2

    def _copy(self, i, slot):
        return pltpu.make_async_copy(
            self.vmem.at[slot], self.hbm.at[self.index(i)], self.sem.at[slot])

    def push(self, i, val):
        slot = _slot(i, self.depth)
        @_when(i >= self.depth)
        def _():
            self._copy(i - self.depth, slot).wait()
        self.vmem[slot] = val
        self._copy(i, slot).start()

    def drain(self, n_tiles):
        for j in range(self.depth):
            @_when(j < n_tiles)
            def _(j=j):
                i = n_tiles - 1 - j
                self._copy(i, _slot(i, self.depth)).wait()


def ring_scratch(depth: int, tile_shape: Tuple[int, ...], dtype) -> Any:
    """VMEM ring-buffer scratch shape for a TileStream."""
    return pltpu.VMEM((depth, *tile_shape), dtype)


def dma_sems(depth: int) -> Any:
    return pltpu.SemaphoreType.DMA((depth,))


def scratch_for(spec: Union[PipelineSpec, Strategy],
                tile_shape: Tuple[int, ...], dtype, *, depth: int = 2):
    """(vmem_ring, dma_sems, staging) scratch specs for one TileStream.

    ``staging`` is the SYNC register-round-trip buffer (full tile shape so
    ``emit_sync(..., staging=...)`` can land the VMEM->VMEM copy); for every
    other strategy it is a minimal placeholder so kernel scratch arity stays
    the same across strategies.  Kernels must not hand-roll staging buffers.
    """
    spec = as_spec(spec, depth=depth)
    stage_shape = tile_shape if spec.strategy == Strategy.SYNC \
        else tuple(1 for _ in tile_shape)
    return (ring_scratch(spec.ring_depth, tile_shape, dtype),
            dma_sems(spec.ring_depth),
            pltpu.VMEM(stage_shape, dtype))


def writeback_scratch(spec: Union[PipelineSpec, Strategy],
                      tile_shape: Tuple[int, ...], dtype):
    """(vmem_ring, dma_sems) for a WriteBack drain at ``spec.out_depth``."""
    d = spec.out_depth if isinstance(spec, PipelineSpec) else 2
    return ring_scratch(d, tile_shape, dtype), dma_sems(d)
