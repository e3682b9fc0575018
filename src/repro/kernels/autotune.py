"""Per-bucket block-shape autotuning for the Pallas kernels.

The kernels in this package take ``(block_b, block_i, block_k)`` tile shapes;
until this module existed every caller got the hardcoded ``DEFAULT_BLOCK_*``
(128³), re-clamped per call.  The serving engine instead solves on a small
set of ``(N, batch)`` buckets, so the right tiles can be *picked once per
bucket* — at engine install time — and reused for the lifetime of the jit
executable.

The tuner is analytic, not search-based: no kernel is timed at run time.
The model maximizes tile size (fewer grid steps, higher MXU occupancy, fewer
HBM round-trips per operand byte) subject to

* hardware alignment — power-of-two tiles, shrunk toward the operand extent
  so padding waste stays bounded (``_pick_block`` semantics), and
* a VMEM budget per kind.  The ``"step"`` tiles of the streaming kernels grow
  until the working set of one grid step, counted as the pipeline holds it
  with two buffers of every blocked operand
  (:func:`repro.kernels.coupling_kernel.step_vmem_bytes`), would pass
  :data:`STEP_VMEM_BUDGET_BYTES`: on a v5e each grid step costs about
  0.36 µs whatever it moves, so a (32768 × 131072) W block streams at its
  HBM roofline only in wide tiles.  ``"matvec"`` shrinks
  from fixed preferences until one buffer of each operand
  (:func:`repro.kernels.coupling_kernel.vmem_bytes`) fits
  :data:`VMEM_BUDGET_BYTES`; ``"multi"`` sizes its resident W against
  :data:`MULTI_VMEM_BUDGET_BYTES`.

Results are cached on the bucket key, so repeated engine installs (and the
jit retrace they must *not* cause) resolve to identical static block tuples;
``TUNE_COUNTER`` exposes hit/miss counts for the trace-flatness tests, and
``cache_info()`` is surfaced by the engine/serving ``stats()``.
"""

from __future__ import annotations

import collections
from typing import Dict, Iterator, NamedTuple, Tuple

from repro.kernels import coupling_kernel as _k

#: Budget of the ``"matvec"`` tiles, one buffer of each operand counted
#: (:func:`repro.kernels.coupling_kernel.vmem_bytes`).
VMEM_BUDGET_BYTES = (16 * 2**20) // 4

#: Budget of one ``"step"`` grid step with both pipeline buffers of every
#: blocked operand counted (:func:`repro.kernels.coupling_kernel.step_vmem_bytes`):
#: the v5e's 16 MiB scoped VMEM less 2 MiB for the values the kernel body
#: keeps live (the dot's partial sums, the epilogue's selects).
STEP_VMEM_BUDGET_BYTES = 14 * 2**20

#: Budget for the multi-cycle kernel's pipelined blocks
#: (:func:`repro.kernels.coupling_kernel.multi_vmem_bytes`): the 16 MiB
#: scoped-VMEM limit of a v5e kernel, less 2 MiB for the values the
#: in-kernel cycle loop keeps live.
MULTI_VMEM_BUDGET_BYTES = 14 * 2**20

#: Largest padded N whose resident (N, N) int8 weight tile fits the
#: multi-cycle kernel's budget (two N² buffers = 8 MiB at N = 2048, leaving
#: the rest for phase/bookkeeping blocks).  Single source of truth —
#: ``repro.core.dynamics._multi_kernel_eligible`` gates on it.
MULTI_KERNEL_MAX_N = 2048

#: Kinds a block tuple can be tuned for; one cache entry per (kind, bucket).
KINDS = ("step", "matvec", "multi")

#: The (N, batch) grid the serving/engine stack actually buckets to; the
#: static VMEM checker (``repro.analysis.vmem``) and the kernel benchmarks
#: sweep exactly this grid via :func:`iter_buckets`.
N_BUCKETS = (16, 32, 48, 64, 128, 256, 506, 512, 1024, 2048, 4096)
BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)

#: Cache hits/misses, incremented at resolution time.  Flat misses across
#: repeated engine installs == the tuner re-resolved nothing.
TUNE_COUNTER: collections.Counter = collections.Counter()


class BlockConfig(NamedTuple):
    """One tuned tile shape; fields are static jit arguments downstream."""

    block_b: int
    block_i: int
    block_k: int


_CACHE: Dict[Tuple[str, int, int, int], BlockConfig] = {}


def _pick(size: int, preferred: int, minimum: int = 8) -> int:
    """Largest power-of-two block ≤ preferred without gross padding waste."""
    b = preferred
    while b > minimum and b > size:
        b //= 2
    return max(b, minimum)


def _lane_tile(size: int, preferred: int, minimum: int = 8) -> int:
    """A tile for an axis that lands on the 128-wide lane dimension of some
    block: a multiple of 128, or — for extents below 128 — the whole extent
    rounded up to a multiple of 8.  A TPU block's last dimension must span
    whole lane groups or the whole (padded) operand: a 32-wide tile of a
    64-wide operand does not lower, and neither does a one-row MAC tile."""
    if size < 128:
        return -(-size // minimum) * minimum
    return _pick(size, preferred, minimum)


def _shrink_to_budget(bb: int, bi: int, bk: int, minimum: int = 8) -> BlockConfig:
    """Halve the largest tile axis until the working set fits the budget."""
    while _k.vmem_bytes(bb, bi, bk, fused=True) > VMEM_BUDGET_BYTES:
        largest = max(bb, bi, bk)
        if largest <= minimum:
            break
        if bk == largest:
            bk //= 2
        elif bi == largest:
            bi //= 2
        else:
            bb //= 2
    return BlockConfig(bb, bi, bk)


def _step_tiles(bb: int, m: int, n: int) -> BlockConfig:
    """The streaming kernels' tiles: as few grid steps as VMEM allows.

    From the lane tile (or the whole extent, below 128), double the narrower
    of ``block_i`` and ``block_k`` — ``block_k`` on a tie — while the doubled
    tile still divides its lane-padded extent (so W is never padded further,
    and a large W is never copied) and the double-buffered working set fits
    :data:`STEP_VMEM_BUDGET_BYTES`.
    """
    bi, bk = _lane_tile(m, 128), _lane_tile(n, 128)

    def can_double(tile: int, size: int) -> bool:
        return size >= 128 and (-(-size // 128) * 128) % (2 * tile) == 0

    def fits(bi: int, bk: int) -> bool:
        return max(_k.step_vmem_bytes(bb, bi, bk).values()) <= STEP_VMEM_BUDGET_BYTES

    while True:
        grow_k = can_double(bk, n) and fits(bi, 2 * bk)
        grow_i = can_double(bi, m) and fits(2 * bi, bk)
        if grow_k and (bk <= bi or not grow_i):
            bk *= 2
        elif grow_i:
            bi *= 2
        else:
            return BlockConfig(bb, bi, bk)


def blocks_for(kind: str, *, n: int, batch: int, m: int | None = None) -> BlockConfig:
    """The tuned ``(block_b, block_i, block_k)`` for one ``(N, batch)`` bucket.

    ``m`` is the output-row extent when it differs from ``n`` (the Ising
    solver contracts (M, N) row slabs).  Pure and cached: the same bucket
    key always returns the same tuple, so jit cache keys built from it are
    stable across engine installs.
    """
    if kind not in KINDS:
        raise ValueError(f"unknown autotune kind {kind!r}; expected one of {KINDS}")
    if n <= 0 or batch <= 0:
        raise ValueError(f"blocks_for: need positive bucket dims, got n={n} batch={batch}")
    m = n if m is None else m
    key = (kind, m, n, batch)
    hit = _CACHE.get(key)
    if hit is not None:
        TUNE_COUNTER["hit"] += 1
        return hit
    TUNE_COUNTER["miss"] += 1
    bb = _pick(batch, 128)
    if kind == "multi":
        # 1-D grid over the batch; the weight matrix is a resident (N, N)
        # tile, so only block_b is free.  block_i/block_k are reported as N
        # for the VMEM accounting.  The unpacked layout is the worst case.
        n_padded = -(-n // 128) * 128
        while bb > 8 and _k.multi_vmem_bytes(bb, n_padded, packed=False) > MULTI_VMEM_BUDGET_BYTES:
            bb //= 2
        cfg = BlockConfig(bb, n, n)
    elif kind == "matvec":
        # f32 GEMV: long contraction blocks amortize the weight stream; the
        # batch extent is decode-sized.
        bb = _pick(batch, 8)
        bm = _lane_tile(m, _k.DEFAULT_BLOCK_I)
        bk = _pick(n, 512, minimum=128)
        cfg = _shrink_to_budget(bb, bm, bk, minimum=8)
    else:
        cfg = _step_tiles(bb, m, n)
    _CACHE[key] = cfg
    return cfg


def warm(*, n: int, batch: int, kinds: Tuple[str, ...] = ("step", "multi")) -> None:
    """Pre-resolve the block tuples for one bucket (engine install time).

    Idempotent and cheap; the point is that every later kernel call for this
    bucket — including ones inside freshly traced executables — is a pure
    cache hit, so install→solve→install→solve keeps the trace counters flat.
    """
    for kind in kinds:
        blocks_for(kind, n=n, batch=batch)


def iter_buckets(
    kinds: Tuple[str, ...] = KINDS,
) -> Iterator[Tuple[str, int, int]]:
    """Every ``(kind, n, batch)`` bucket the tuner can be asked for.

    The one sweep shared by the static VMEM checker
    (``repro.analysis.vmem``) and ``benchmarks/kernels.py`` — a budget
    regression in a bucket neither happens to exercise is impossible when
    both enumerate the same grid.  Multi buckets whose padded N exceeds
    :data:`MULTI_KERNEL_MAX_N` are skipped (``_multi_kernel_eligible``
    never routes them to the kernel).
    """
    for kind in kinds:
        if kind not in KINDS:
            raise ValueError(f"unknown autotune kind {kind!r}; expected one of {KINDS}")
        for n in N_BUCKETS:
            if kind == "multi" and -(-n // 128) * 128 > MULTI_KERNEL_MAX_N:
                continue
            for batch in BATCH_BUCKETS:
                yield kind, n, batch


def grid_steps(kind: str, m: int, n: int, batch: int, cfg: BlockConfig) -> int:
    """Grid steps the tiles cut a ``(kind, m, n, batch)`` operand set into."""
    steps = -(-batch // cfg.block_b)
    if kind != "multi":
        steps *= -(-m // cfg.block_i) * -(-n // cfg.block_k)
    return steps


def cache_info() -> Dict[str, object]:
    """Tuner cache summary for ``stats()`` surfaces: entries, hits, misses,
    and per kind the largest grid-step count among the resolved entries."""
    most: Dict[str, int] = {}
    for (kind, m, n, batch), cfg in _CACHE.items():
        most[kind] = max(most.get(kind, 0), grid_steps(kind, m, n, batch, cfg))
    return {
        "entries": len(_CACHE),
        "hits": int(TUNE_COUNTER["hit"]),
        "misses": int(TUNE_COUNTER["miss"]),
        "max_grid_steps": most,
    }


def clear_cache() -> None:
    """Drop all tuned entries and counters (tests)."""
    _CACHE.clear()
    TUNE_COUNTER.clear()
