"""Pure-jnp oracles for the Pallas kernels.

Every kernel in this package has its reference semantics here; the kernel
tests sweep shapes/dtypes and assert allclose (exact for the integer paths)
against these functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.checks import require_int_dtype


def coupling_sum_ref(w: jax.Array, sigma: jax.Array) -> jax.Array:
    """S = σ Wᵀ: (B, N) int8 spins × (N, N) int8 weights → (B, N) int32."""
    require_int_dtype(w, "w")
    return jnp.einsum(
        "ij,bj->bi",
        w.astype(jnp.int32),
        sigma.astype(jnp.int32),
        preferred_element_type=jnp.int32,
    )


def onn_step_ref(w: jax.Array, sigma: jax.Array, bias: jax.Array | None = None) -> jax.Array:
    """Fused coupling sum + sign alignment: σ' = sign(S), ties keep σ."""
    s = coupling_sum_ref(w, sigma)
    if bias is not None:
        s = s + require_int_dtype(bias, "bias").astype(jnp.int32)[None, :]
    return jnp.where(s > 0, 1, jnp.where(s < 0, -1, sigma.astype(jnp.int32))).astype(
        jnp.int8
    )


def phase_step_ref(
    w: jax.Array,
    sigma: jax.Array,
    bias: jax.Array,
    phase: jax.Array,
    half: int,
) -> jax.Array:
    """Fused coupling sum + phase alignment (paper §2.3), int32 phases.

    ``phase``: (B, N) int32 rotating-frame phase counters.  S > 0 snaps the
    oscillator in phase with the reference (phase 0), S < 0 in anti-phase
    (phase ``half``), S == 0 keeps the current phase — the whole functional-
    mode oscillation cycle in one map.
    """
    s = coupling_sum_ref(w, sigma) + require_int_dtype(bias, "bias").astype(jnp.int32)[None, :]
    return jnp.where(
        s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), phase.astype(jnp.int32))
    )


def hybrid_coupling_sum_ref(w: jax.Array, sigma: jax.Array, parallel: int) -> jax.Array:
    """Serialized-MAC coupling sum, pass by pass (hybrid datapath oracle).

    An explicit Python loop over the ``ceil(N / parallel)`` passes — each
    pass accumulates a ``parallel``-wide slice of every row into the int32
    accumulator, including the ragged final pass — deliberately independent
    of both the ``lax.scan`` reference and the pass-group kernels it checks.
    """
    if parallel <= 0:
        raise ValueError(f"parallel must be positive, got {parallel}")
    n = w.shape[1]
    acc = jnp.zeros((sigma.shape[0], w.shape[0]), jnp.int32)
    for start in range(0, n, parallel):
        wp = w[:, start : start + parallel].astype(jnp.int32)
        sp = sigma[:, start : start + parallel].astype(jnp.int32)
        acc = acc + jnp.einsum("ip,bp->bi", wp, sp, preferred_element_type=jnp.int32)
    return acc


def hybrid_phase_step_ref(
    w: jax.Array,
    sigma: jax.Array,
    bias: jax.Array,
    phase: jax.Array,
    half: int,
    parallel: int,
) -> jax.Array:
    """Serialized-MAC coupling sum + the phase-align epilogue (int32 phases)."""
    s = hybrid_coupling_sum_ref(w, sigma, parallel) + require_int_dtype(
        bias, "bias"
    ).astype(jnp.int32)[None, :]
    return jnp.where(
        s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), phase.astype(jnp.int32))
    )


def phase_step_packed_ref(
    w: jax.Array, bias: jax.Array, phase: jax.Array, half: int
) -> jax.Array:
    """Packed-operand cycle oracle: σ derived from θ, then phase alignment.

    ``phase``: (B, N) *unpacked* int counters (the packing is a transport
    layout, not a semantic change); σ = +1 iff θ < half.  Matches
    ``phase_step_packed_pallas`` fed ``pack_block_halves(phase, width)``.
    """
    sigma = jnp.where(phase.astype(jnp.int32) < half, 1, -1).astype(jnp.int8)
    return phase_step_ref(w, sigma, bias, phase, half)


def phase_step_multi_ref(
    w: jax.Array,
    bias: jax.Array,
    phase: jax.Array,
    prev_phase: jax.Array,
    t: jax.Array,
    settle_cycle: jax.Array,
    settled: jax.Array,
    cycled: jax.Array,
    frozen: jax.Array,
    frozen_p2: jax.Array,
    freeze_cycle: jax.Array,
    *,
    half: int,
    chunk: int,
    max_cycles: int,
):
    """``chunk`` functional-mode cycles + settle/freeze bookkeeping, oracle.

    Same 9-tuple contract as ``phase_step_multi_pallas`` (unpacked int32
    phases, (B, 1) int32 bookkeeping columns) as an explicit Python loop —
    deliberately a third implementation, independent of both the kernel and
    the fused-chunk jnp path in ``repro.core.dynamics``.
    """
    ph = phase.astype(jnp.int32)
    prev = prev_phase.astype(jnp.int32)
    t, sc = t.astype(jnp.int32), settle_cycle.astype(jnp.int32)
    sd, cy = settled.astype(jnp.int32), cycled.astype(jnp.int32)
    fz, fp2 = frozen.astype(jnp.int32), frozen_p2.astype(jnp.int32)
    fc = freeze_cycle.astype(jnp.int32)
    for _ in range(chunk):
        sigma = jnp.where(ph < half, 1, -1).astype(jnp.int8)
        s = coupling_sum_ref(w, sigma) + require_int_dtype(bias, "bias").astype(
            jnp.int32
        )[None, :]
        nph = jnp.where(s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), ph))
        active = (fz == 0) & (t < max_cycles)
        not_first = t > 0
        lane_unchanged = jnp.all(nph == ph, axis=-1, keepdims=True)
        phase_p2 = jnp.all(nph == prev, axis=-1, keepdims=True)
        is_cycle2 = phase_p2 & ~lane_unchanged & not_first
        sc = jnp.where(active & lane_unchanged & (sd == 0), t, sc)
        sd = jnp.where(active & lane_unchanged, 1, sd)
        cy = jnp.where(active & is_cycle2 & (sd == 0), 1, cy)
        newly = active & (lane_unchanged | is_cycle2)
        ph, prev = jnp.where(active, nph, ph), jnp.where(active, ph, prev)
        fp2 = jnp.where(newly & is_cycle2, 1, fp2)
        fc = jnp.where(newly, t + 1, fc)
        fz = jnp.where(newly, 1, fz)
        t = jnp.where(active, t + 1, t)
    return ph, prev, sc, sd, cy, fz, fp2, fc, t


def quantized_matvec_ref(w_q: jax.Array, scale: jax.Array, x: jax.Array) -> jax.Array:
    """General quantized GEMV: y = (w_q · scale) @ x in f32.

    ``w_q``: (M, K) int8; ``scale``: per-row (M,) or scalar f32; ``x``: (B, K) f32.
    """
    acc = jnp.einsum(
        "mk,bk->bm", w_q.astype(jnp.float32), x.astype(jnp.float32)
    )
    return acc * jnp.broadcast_to(scale, acc.shape[-1:])
