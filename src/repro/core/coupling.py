"""Coupling-element arithmetic: parallel (recurrent) vs serialized (hybrid).

Paper §2.3 / §3.  The recurrent architecture computes every oscillator's
weighted input sum with a combinational adder tree (N² adders); the hybrid
architecture serializes each row through a single MAC on a fast clock,
streaming weights from addressable memory.  Both compute *exactly* the same
integer sum — the architectures differ in hardware cost and timing, not in
arithmetic — and the implementations below are the executable versions of
both schedules.  The blocked/chunked serial schedule is the schedule the
Pallas TPU kernel (``repro.kernels``) uses: the paper's BRAM streaming maps
to HBM→VMEM block streaming.

All sums are exact int32 (see ``quantization.accumulator_bits``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.checks import require_int_dtype


def _check(w: jax.Array, sigma: jax.Array) -> None:
    # w is (M, N): M output rows contracting over N spins.  M == N for a
    # full coupling matrix; M < N serves row slabs (e.g. the Ising solver's
    # staggered update groups evaluate the field only at group members).
    if w.ndim != 2:
        raise ValueError(f"coupling matrix must be 2-d, got {w.shape}")
    if sigma.shape[-1] != w.shape[1]:
        raise ValueError(f"spin vector {sigma.shape} incompatible with {w.shape}")


def weighted_sum_parallel(w: jax.Array, sigma: jax.Array) -> jax.Array:
    """Recurrent-architecture weighted sum: S_i = Σ_j W_ij σ_j, all at once.

    ``w``: (N, N) int8, ``sigma``: (..., N) int8 in {−1, +1}.  Returns
    (..., N) int32.  The combinational adder tree of Fig. 4 — one fully
    parallel contraction.
    """
    _check(w, sigma)
    require_int_dtype(w, "w")
    return jnp.einsum(
        "ij,...j->...i",
        w.astype(jnp.int32),
        sigma.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )


def weighted_sum_serial(w: jax.Array, sigma: jax.Array, chunk: int = 1) -> jax.Array:
    """Hybrid-architecture weighted sum: serialized accumulation (Fig. 5).

    Accumulates over inputs ``chunk`` at a time with a ``lax.scan`` — the
    executable model of the fast-clock counter + single MAC (``chunk=1``) or
    of P parallel MAC lanes (``chunk=P``, ``ceil(N / P)`` passes).  It is
    the ``hybrid`` backend of ``repro.core.dynamics``, with
    ``chunk = ONNConfig.hybrid_parallel``.
    Bit-exact to :func:`weighted_sum_parallel` by integer associativity; when
    ``chunk`` does not divide N the contraction dimension is zero-padded (the
    hardware analogue: the MAC idles on the tail fast-clock edges), which
    leaves the integer sum unchanged.
    """
    _check(w, sigma)
    require_int_dtype(w, "w")
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    n_rows, n = w.shape
    pad = (-n) % chunk
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        sigma = jnp.pad(sigma, [(0, 0)] * (sigma.ndim - 1) + [(0, pad)])
    steps = (n + pad) // chunk
    # (steps, N, chunk) weight blocks; (steps, ..., chunk) spin blocks.
    w_blocks = w.astype(jnp.int32).reshape(n_rows, steps, chunk).transpose(1, 0, 2)
    s_blocks = jnp.moveaxis(
        sigma.astype(jnp.int32).reshape(*sigma.shape[:-1], steps, chunk), -2, 0
    )

    def body(acc, blocks):
        wb, sb = blocks  # (N, chunk), (..., chunk)
        acc = acc + jnp.einsum("ic,...c->...i", wb, sb, preferred_element_type=jnp.int32)
        return acc, None

    init = jnp.zeros((*sigma.shape[:-1], n_rows), dtype=jnp.int32)
    acc, _ = jax.lax.scan(body, init, (w_blocks, s_blocks))
    return acc


def adders_required_parallel(n: int) -> int:
    """Adder count of the recurrent architecture: N rows × (N−1) adders."""
    return n * (n - 1)


def adders_required_serial(n: int) -> int:
    """Adder count of the hybrid architecture: one accumulator per row."""
    return n


def serialization_factor(n: int, overhead_clocks: int = 2, parallel: int = 1) -> int:
    """Fast-clock cycles needed per slow-clock phase update (paper §3).

    With ``parallel`` MAC lanes per oscillator (P coupling values consumed
    per fast edge) the counter walks ``ceil(N / P)`` passes, plus a small
    control overhead (reset and result-hold registration).  ``parallel=1``
    is the paper's single-MAC hybrid: N + overhead fast clocks.
    """
    if parallel <= 0:
        raise ValueError(f"parallel must be positive, got {parallel}")
    return -(-n // parallel) + overhead_clocks
