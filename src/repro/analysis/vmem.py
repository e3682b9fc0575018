"""Static Pallas VMEM checker: every autotune bucket, symbolically.

The kernels in ``repro.kernels.coupling_kernel`` validate their own block
shapes at call time, but a budget regression in a bucket no test happens to
exercise ships silently.  This module closes that hole *statically*: it
resolves the tuner's block choice for **every** ``(kind, N, batch)`` bucket
(:func:`repro.kernels.autotune.iter_buckets`), evaluates the per-grid-step
working set of each kernel that runs with those blocks — the same BlockSpec
accounting the kernels use, extended with the bias/phase/scratch operands
the matvec tuner's quick estimate omits; for the streaming
"step" kernels, two pipeline buffers of every blocked operand — and
compares against the committed budgets (``STEP_VMEM_BUDGET_BYTES`` /
``VMEM_BUDGET_BYTES`` / ``MULTI_VMEM_BUDGET_BYTES``).

No kernel is compiled and no array is built; the check is pure integer
arithmetic over the tuner's outputs, so it runs in CI in milliseconds via
``repro-lint --vmem``.
"""

from __future__ import annotations

import dataclasses
import sys
from typing import Dict, Iterable, List, TextIO, Tuple

from repro.kernels import autotune
from repro.kernels import coupling_kernel as _k


@dataclasses.dataclass(frozen=True)
class BucketReport:
    """Worst-case working set of one tuner bucket."""

    kind: str
    n: int
    batch: int
    blocks: Tuple[int, int, int]
    kernel: str  # the kernel with the largest working set for this kind
    bytes: int
    budget: int

    @property
    def ok(self) -> bool:
        return self.bytes <= self.budget

    def render(self) -> str:
        status = "ok" if self.ok else "OVER"
        return (
            f"{self.kind:7s} n={self.n:<5d} b={self.batch:<4d} "
            f"blocks={self.blocks!r:18s} {self.kernel:18s} "
            f"{self.bytes:>9,d} / {self.budget:>9,d} B  {status}"
        )


def _pad128(n: int) -> int:
    return -(-n // 128) * 128


def _matvec_working_sets(bb: int, bm: int, bk: int) -> Dict[str, int]:
    x = bb * bk * 4  # f32 activations
    w = bm * bk  # int8 weight tile
    scale = bm * 4
    out = bb * bm * 4
    acc = bb * bm * 4
    return {"quantized_matvec": x + w + scale + out + acc}


def check_bucket(kind: str, n: int, batch: int) -> BucketReport:
    """Resolve the tuner's blocks for one bucket and size its worst kernel."""
    blocks = autotune.blocks_for(kind, n=n, batch=batch)
    bb, bi, bk = blocks
    if kind == "multi":
        sets = {
            "phase_step_multi": _k.multi_vmem_bytes(bb, _pad128(n), packed=False)
        }
        budget = autotune.MULTI_VMEM_BUDGET_BYTES
    elif kind == "matvec":
        sets = _matvec_working_sets(bb, bi, bk)
        budget = autotune.VMEM_BUDGET_BYTES
    else:
        # both pipeline buffers of each blocked operand, as the tuner counts
        sets = _k.step_vmem_bytes(bb, bi, bk)
        budget = autotune.STEP_VMEM_BUDGET_BYTES
    kernel = max(sets, key=sets.__getitem__)
    return BucketReport(
        kind=kind, n=n, batch=batch, blocks=tuple(blocks),
        kernel=kernel, bytes=sets[kernel], budget=budget,
    )


def check_all(
    kinds: Tuple[str, ...] = autotune.KINDS,
) -> List[BucketReport]:
    """One :class:`BucketReport` per ``iter_buckets`` bucket.

    Resolving blocks populates the tuner cache; the hit/miss counters are
    restored afterwards so a static check never perturbs the trace-hygiene
    accounting (``tracegate`` reads ``TUNE_COUNTER``).
    """
    counter_before = dict(autotune.TUNE_COUNTER)
    try:
        return [
            check_bucket(kind, n, batch)
            for kind, n, batch in autotune.iter_buckets(kinds)
        ]
    finally:
        autotune.TUNE_COUNTER.clear()
        autotune.TUNE_COUNTER.update(counter_before)


def report(out: TextIO = sys.stdout, reports: Iterable[BucketReport] | None = None) -> int:
    """Print the over-budget buckets (and a summary); return the failure count."""
    reports = list(check_all() if reports is None else reports)
    failures = [r for r in reports if not r.ok]
    for r in failures:
        out.write(r.render() + "\n")
    worst = max(reports, key=lambda r: r.bytes / r.budget)
    out.write(
        f"vmem: {len(reports)} buckets checked, {len(failures)} over budget; "
        f"tightest is {worst.kind} n={worst.n} b={worst.batch} at "
        f"{100.0 * worst.bytes / worst.budget:.1f}% "
        f"({worst.bytes:,d} / {worst.budget:,d} B, kernel {worst.kernel})\n"
    )
    return len(failures)
