"""Pallas TPU kernels for the ONN coupling computation.

The paper's hybrid architecture streams each oscillator's weight row from
addressable memory through a single MAC on a fast clock.  The TPU-native
version of that insight: stream quantized weight *blocks* HBM→VMEM and
accumulate partial sums on-chip, with the MXU playing the role of the DSP
MAC array.  The serial counter of the FPGA design becomes the innermost grid
dimension; the BRAM row becomes a VMEM tile; the slow/fast clock-domain pair
becomes the (outer grid step, inner contraction step) pair.

Kernels
-------
* ``coupling_sum``:   S[b,i]  = Σ_j W[i,j] σ[b,j]           (int8 → int32)
* ``onn_step_fused``: σ'[b,i] = sign-align(S[b,i] + h[i])    (fused epilogue)
* ``quantized_matvec``: y = (W_q · scale) @ x                 (int8 × f32 GEMV)

All are validated against ``ref.py`` in interpret mode on the CPU and
compiled for a described TPU v5e by ``tests/test_tpu_compile.py``; block
shapes are hardware-aligned for the 128×128 MXU and the (32, 128) int8 VMEM
tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Hardware-aligned defaults (tunable per §Perf): MXU lane = 128;
# int8 sublane = 32.  Working set per step for the fused kernel:
#   σ tile (bb×bk) + W tile (bi×bk) + acc (bb×bi ×4B)  ≤ VMEM (~16 MiB/core).
DEFAULT_BLOCK_B = 128
DEFAULT_BLOCK_I = 128
DEFAULT_BLOCK_K = 128


def pad_to_blocks(x: jax.Array, multiples, value=0) -> jax.Array:
    """Zero-pad each axis of ``x`` up to the next multiple of ``multiples``.

    ``multiples`` is one int per axis (0/1 → leave the axis alone).  This is
    the same treatment the serial backend gives N not divisible by its chunk:
    padded rows/columns carry zeros, which contribute nothing to the integer
    sums, so callers can slice the result back to the original extent.  The
    ``*_pallas`` entry points below require pre-padded shapes and point here
    when they reject a ragged one.
    """
    if len(multiples) != x.ndim:
        raise ValueError(
            f"pad_to_blocks: {len(multiples)} multiples for {x.ndim}-d input"
        )
    widths = []
    for size, m in zip(x.shape, multiples):
        pad = 0 if m in (0, 1) else (-size) % m
        widths.append((0, pad))
    if not any(w for _, w in widths):
        return x
    return jnp.pad(x, widths, constant_values=value)


def _require(ok: bool, msg: str) -> None:
    """Shape-contract check that survives ``python -O`` (unlike assert)."""
    if not ok:
        raise ValueError(msg)


def vmem_bytes(bb: int, bi: int, bk: int, fused: bool = True) -> int:
    """VMEM working-set estimate for one grid step (for block-size tuning)."""
    sig = bb * bk  # int8
    w = bi * bk  # int8
    acc = bb * bi * 4  # int32 accumulator
    sig_self = bb * bi if fused else 0  # tie-keeping σ view
    out = bb * bi * (1 if fused else 4)
    return sig + w + acc + sig_self + out


# ---------------------------------------------------------------------------
# coupling_sum: S = σ @ Wᵀ, int32 accumulation in the output block.
# ---------------------------------------------------------------------------


def _coupling_sum_kernel(sigma_ref, w_ref, out_ref):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # (bb, bk) · (bi, bk)ᵀ → (bb, bi), exact int32 accumulation (MXU int8 path).
    partial = jax.lax.dot_general(
        sigma_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out_ref[...] += partial


def coupling_sum_pallas(
    sigma: jax.Array,
    w: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_i: int = DEFAULT_BLOCK_I,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """S[b,i] = Σ_j W[i,j] σ[b,j].  Shapes must be pre-padded to block multiples."""
    b, n = sigma.shape
    ni, nk = w.shape
    _require(n == nk, f"coupling_sum_pallas: sigma N={n} != weights N={nk}")
    _require(
        b % block_b == 0 and ni % block_i == 0 and nk % block_k == 0,
        f"coupling_sum_pallas: shapes (b={b}, ni={ni}, nk={nk}) not multiples "
        f"of blocks ({block_b}, {block_i}, {block_k}); pad with pad_to_blocks",
    )
    grid = (ni // block_i, b // block_b, nk // block_k)
    return pl.pallas_call(
        _coupling_sum_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, bb, k: (bb, k)),
            pl.BlockSpec((block_i, block_k), lambda i, bb, k: (i, k)),
        ],
        out_specs=pl.BlockSpec((block_b, block_i), lambda i, bb, k: (bb, i)),
        out_shape=jax.ShapeDtypeStruct((b, ni), jnp.int32),
        interpret=interpret,
    )(sigma, w)


# ---------------------------------------------------------------------------
# onn_step_fused: accumulate in VMEM scratch, epilogue applies the phase-
# alignment sign rule (paper §2.3) — the reference-signal generation fused
# into the coupling computation.
# ---------------------------------------------------------------------------


def _onn_step_kernel(sigma_ref, w_ref, bias_ref, sigma_self_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        sigma_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        s = acc_ref[...] + bias_ref[...].astype(jnp.int32)  # (bb, bi)
        keep = sigma_self_ref[...].astype(jnp.int32)
        out_ref[...] = jnp.where(s > 0, 1, jnp.where(s < 0, -1, keep)).astype(jnp.int8)


def onn_step_pallas(
    sigma: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    *,
    block_b: int = DEFAULT_BLOCK_B,
    block_i: int = DEFAULT_BLOCK_I,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Fused σ' = sign-align(W σ + h); ties keep the current spin."""
    b, n = sigma.shape
    ni, nk = w.shape
    _require(n == nk, f"onn_step_pallas: sigma N={n} != weights N={nk}")
    _require(
        bias.shape == (ni,),
        f"onn_step_pallas: bias {bias.shape} != ({ni},)",
    )
    _require(
        b % block_b == 0 and ni % block_i == 0 and nk % block_k == 0,
        f"onn_step_pallas: shapes (b={b}, ni={ni}, nk={nk}) not multiples "
        f"of blocks ({block_b}, {block_i}, {block_k}); pad with pad_to_blocks",
    )
    grid = (ni // block_i, b // block_b, nk // block_k)
    bias2d = bias.reshape(1, -1)
    return pl.pallas_call(
        _onn_step_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, bb, k: (bb, k)),
            pl.BlockSpec((block_i, block_k), lambda i, bb, k: (i, k)),
            pl.BlockSpec((1, block_i), lambda i, bb, k: (0, i)),
            pl.BlockSpec((block_b, block_i), lambda i, bb, k: (bb, i)),
        ],
        out_specs=pl.BlockSpec((block_b, block_i), lambda i, bb, k: (bb, i)),
        out_shape=jax.ShapeDtypeStruct((b, ni), jnp.int8),
        scratch_shapes=[pltpu.VMEM((block_b, block_i), jnp.int32)],
        interpret=interpret,
    )(sigma, w, bias2d, sigma)


# ---------------------------------------------------------------------------
# phase_step_fused: the batched-native functional-mode cycle.  Same blocked
# int8 matmul as onn_step_fused, but the epilogue applies the *phase*
# alignment rule (paper §2.3) instead of the spin sign rule, so one kernel
# launch advances the whole (B, N) phase state by one oscillation cycle —
# ties keep the current phase counter, which may be non-canonical (any value
# in [0, 2**phase_bits)), not just the ±1-spin phases.
# ---------------------------------------------------------------------------


def _phase_step_kernel(half: int, sigma_ref, w_ref, bias_ref, phase_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        sigma_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        s = acc_ref[...] + bias_ref[...].astype(jnp.int32)  # (bb, bi)
        keep = phase_ref[...]
        out_ref[...] = jnp.where(
            s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), keep)
        )


def phase_step_pallas(
    sigma: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    phase: jax.Array,
    *,
    half: int,
    block_b: int = DEFAULT_BLOCK_B,
    block_i: int = DEFAULT_BLOCK_I,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Fused θ' = phase-align(W σ + h, θ); S == 0 keeps the current phase.

    ``sigma``: (B, N) int8 spins of ``phase``; ``phase``: (B, N) int32
    counters; ``half`` is the anti-phase counter value (2**phase_bits / 2).
    Shapes must be pre-padded to block multiples (``pad_to_blocks``).
    """
    b, n = sigma.shape
    ni, nk = w.shape
    _require(n == nk, f"phase_step_pallas: sigma N={n} != weights N={nk}")
    _require(bias.shape == (ni,), f"phase_step_pallas: bias {bias.shape} != ({ni},)")
    _require(
        phase.shape == (b, ni),
        f"phase_step_pallas: phase {phase.shape} != ({b}, {ni})",
    )
    _require(
        b % block_b == 0 and ni % block_i == 0 and nk % block_k == 0,
        f"phase_step_pallas: shapes (b={b}, ni={ni}, nk={nk}) not multiples "
        f"of blocks ({block_b}, {block_i}, {block_k}); pad with pad_to_blocks",
    )
    grid = (ni // block_i, b // block_b, nk // block_k)
    bias2d = bias.reshape(1, -1)
    return pl.pallas_call(
        functools.partial(_phase_step_kernel, half),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, bb, k: (bb, k)),
            pl.BlockSpec((block_i, block_k), lambda i, bb, k: (i, k)),
            pl.BlockSpec((1, block_i), lambda i, bb, k: (0, i)),
            pl.BlockSpec((block_b, block_i), lambda i, bb, k: (bb, i)),
        ],
        out_specs=pl.BlockSpec((block_b, block_i), lambda i, bb, k: (bb, i)),
        out_shape=jax.ShapeDtypeStruct((b, ni), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, block_i), jnp.int32)],
        interpret=interpret,
    )(sigma, w, bias2d, phase.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Packed-phase operands: two 4-bit phase counters per byte (the paper's
# precision-matched storage).  The σ operand of the MAC tile and the keep-θ
# operand of the epilogue are both *derived in-register* from one packed
# uint8 array — σ is a function of θ (σ = +1 iff θ < half) — so the kernel
# moves half the σ/phase bytes per tile and the θ bytes shrink 4× vs the
# int32 operand of ``phase_step_pallas``.
# ---------------------------------------------------------------------------


# Kernel transport layout ("block halves"): within every ``width``-wide
# column block, byte j holds counter j in its low nibble and counter
# j + width/2 in its high nibble.  Unpacking is then two lane-aligned
# bit-field extracts and one concatenation — Mosaic has no lowering for the
# (w/2, 2) → w interleave that a layout of adjacent pairs would need.  The
# packed kernels therefore take blocks whose half-width is a multiple of the
# 128-lane tile (:data:`PACKED_BLOCK_MULTIPLE`); the XLA-side converters
# below map between this layout and plain (B, N) counters.

#: Column-block multiple of the packed kernels: half a block must fill whole
#: 128-lane vregs.
PACKED_BLOCK_MULTIPLE = 256


def pack_block_halves(vals: jax.Array, width: int) -> jax.Array:
    """(B, N) counters in [0, 16) → (B, N/2) uint8 in the block-halves layout."""
    b, n = vals.shape
    _require(n % width == 0 and width % 2 == 0,
             f"pack_block_halves: N={n} not a multiple of even width={width}")
    v = vals.astype(jnp.uint8).reshape(b, n // width, 2, width // 2)
    return (v[:, :, 0] | (v[:, :, 1] << 4)).reshape(b, n // 2)


def unpack_block_halves(packed: jax.Array, width: int) -> jax.Array:
    """Inverse of :func:`pack_block_halves`: (B, N/2) uint8 → (B, N) uint8."""
    b, n_half = packed.shape
    _require((2 * n_half) % width == 0,
             f"unpack_block_halves: N={2 * n_half} not a multiple of width={width}")
    p = packed.reshape(b, (2 * n_half) // width, 1, width // 2)
    return jnp.concatenate([p & 0xF, p >> 4], axis=2).reshape(b, 2 * n_half)


def _unpack_nibbles(packed: jax.Array) -> jax.Array:
    """(bb, w/2) block-halves uint8 → (bb, w) int32 counters (in-kernel)."""
    p = packed.astype(jnp.int32)
    return jnp.concatenate([p & 0xF, (p >> 4) & 0xF], axis=-1)


def _pack_nibbles(vals: jax.Array) -> jax.Array:
    """(bb, w) int32 counters in [0, 16) → (bb, w/2) block-halves uint8."""
    h = vals.shape[1] // 2
    return (vals[:, :h] | (vals[:, h:] << 4)).astype(jnp.uint8)


def packed_phase_vmem_bytes(bb: int, bi: int, bk: int) -> int:
    """VMEM working set of one ``phase_step_packed_pallas`` grid step."""
    packed_sig = bb * (bk // 2)  # uint8, two θ per byte
    w = bi * bk  # int8
    acc = bb * bi * 4  # int32 accumulator
    packed_keep = bb * (bi // 2)  # uint8 keep-θ view
    out = bb * bi * 4  # int32 phases out
    return packed_sig + w + acc + packed_keep + out


def _phase_step_packed_kernel(
    half: int, packed_sig_ref, w_ref, bias_ref, packed_keep_ref, out_ref, acc_ref
):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # σ derived in-register from the packed θ tile: σ = +1 iff θ < half.
    theta = _unpack_nibbles(packed_sig_ref[...])
    sigma = jnp.where(theta < half, 1, -1).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        sigma,
        w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        s = acc_ref[...] + bias_ref[...].astype(jnp.int32)  # (bb, bi)
        keep = _unpack_nibbles(packed_keep_ref[...])
        out_ref[...] = jnp.where(
            s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), keep)
        )


def phase_step_packed_pallas(
    packed_phase: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    *,
    half: int,
    block_b: int = DEFAULT_BLOCK_B,
    block_i: int = DEFAULT_BLOCK_I,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Packed-operand θ' = phase-align(W σ(θ) + h, θ), one launch per cycle.

    ``packed_phase``: (B, N/2) uint8, two 4-bit phase counters per byte in
    the block-halves layout at width ``block_k``
    (:func:`pack_block_halves`); both the σ operand of the MAC tile and the
    epilogue's keep-θ view unpack it in-register, so it is the *only*
    per-lane array the kernel reads — which is why ``block_i`` must equal
    ``block_k`` (a multiple of :data:`PACKED_BLOCK_MULTIPLE`).  Returns
    (B, N) int32 phases (``phase_step_pallas`` contract).  Padded θ entries
    must be 0 (σ = +1 against zero weight columns — inert, the
    ``pad_sigma`` convention).
    """
    b, n_half = packed_phase.shape
    ni, nk = w.shape
    _require(ni == nk, f"phase_step_packed_pallas: weights {w.shape} not square")
    _require(
        2 * n_half == nk,
        f"phase_step_packed_pallas: packed N/2={n_half} != weights N={nk}/2",
    )
    _require(bias.shape == (ni,), f"phase_step_packed_pallas: bias {bias.shape} != ({ni},)")
    _require(
        block_i == block_k and block_k % PACKED_BLOCK_MULTIPLE == 0,
        f"phase_step_packed_pallas: blocks ({block_i}, {block_k}) must be equal "
        f"multiples of {PACKED_BLOCK_MULTIPLE}",
    )
    _require(
        b % block_b == 0 and ni % block_i == 0 and nk % block_k == 0,
        f"phase_step_packed_pallas: shapes (b={b}, n={ni}) not multiples of "
        f"blocks ({block_b}, {block_i}, {block_k}); pad with pad_to_blocks",
    )
    grid = (ni // block_i, b // block_b, nk // block_k)
    bias2d = bias.reshape(1, -1)
    return pl.pallas_call(
        functools.partial(_phase_step_packed_kernel, half),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k // 2), lambda i, bb, k: (bb, k)),
            pl.BlockSpec((block_i, block_k), lambda i, bb, k: (i, k)),
            pl.BlockSpec((1, block_i), lambda i, bb, k: (0, i)),
            pl.BlockSpec((block_b, block_i // 2), lambda i, bb, k: (bb, i)),
        ],
        out_specs=pl.BlockSpec((block_b, block_i), lambda i, bb, k: (bb, i)),
        out_shape=jax.ShapeDtypeStruct((b, ni), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_b, block_i), jnp.int32)],
        interpret=interpret,
    )(packed_phase, w, bias2d, packed_phase)


# ---------------------------------------------------------------------------
# phase_step_multi: `chunk` oscillation cycles in ONE kernel launch.  The
# weight matrix is loaded into VMEM once and stays resident; the phase state
# ping-pongs through the fori_loop carry; the per-lane settle/freeze flags
# (the early-exit bookkeeping of repro.core.dynamics._batch_step) are
# computed in the same launch.  This collapses the `settle_chunk` launches
# between two early-exit checks into one — the launch-overhead fix for the
# small-N regime where per-cycle dispatch dominates.
# ---------------------------------------------------------------------------


def multi_vmem_bytes(block_b: int, n: int, packed: bool = False) -> int:
    """Scoped VMEM of the pipelined blocks of one ``phase_step_multi_pallas``
    grid step.

    The pipeline keeps two buffers of every block — the resident W too,
    although its block index never changes — and the phase state is both
    read and written (θ and prev-θ, in and out).  The v5e compiler's scoped
    allocation at N=2048/B=128 unpacked was 16.68 MiB, this estimate plus
    0.65 MiB of in-kernel values.
    """
    w = 2 * n * n  # int8, resident for all `chunk` cycles
    phase = 2 * 4 * block_b * (n // 2 if packed else n * 4)  # θ, prev-θ in and out
    bias = 2 * n * 4
    flags = 2 * 14 * block_b * 4  # seven (bb, 1) int32 columns in and out
    return w + phase + bias + flags


def _phase_step_multi_kernel(
    half: int,
    chunk: int,
    max_cycles: int,
    packed: bool,
    w_ref,
    bias_ref,
    phase_ref,
    prev_ref,
    t_ref,
    settle_ref,
    settled_ref,
    cycled_ref,
    frozen_ref,
    frozen_p2_ref,
    freeze_ref,
    phase_out,
    prev_out,
    settle_out,
    settled_out,
    cycled_out,
    frozen_out,
    frozen_p2_out,
    freeze_out,
    t_out,
):
    n = w_ref.shape[0]
    w = w_ref[...]
    bias = bias_ref[...].astype(jnp.int32)  # (1, n)
    if packed:
        ph0 = _unpack_nibbles(phase_ref[...])
        prev0 = _unpack_nibbles(prev_ref[...])
    else:
        ph0 = phase_ref[...]
        prev0 = prev_ref[...]

    def cycle(_, carry):
        # Exactly repro.core.dynamics._batch_step in functional mode (aux is
        # constant there, so carry-fixed == phase-fixed and the freeze logic
        # collapses to the phase tests below).  Bools ride as int32 {0, 1}.
        ph, prev, t, sc, sd, cy, fz, fp2, fc = carry
        sigma = jnp.where(ph < half, 1, -1).astype(jnp.int8)
        s = (
            jax.lax.dot_general(
                sigma,
                w,
                dimension_numbers=(((1,), (1,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            + bias
        )
        nph = jnp.where(s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), ph))
        active = (fz == 0) & (t < max_cycles)  # (bb, 1)
        not_first = t > 0
        lane_unchanged = jnp.all(nph == ph, axis=-1, keepdims=True)
        phase_p2 = jnp.all(nph == prev, axis=-1, keepdims=True)
        is_cycle2 = phase_p2 & ~lane_unchanged & not_first
        sc = jnp.where(active & lane_unchanged & (sd == 0), t, sc)
        sd = jnp.where(active & lane_unchanged, 1, sd)
        cy = jnp.where(active & is_cycle2 & (sd == 0), 1, cy)
        newly = active & (lane_unchanged | is_cycle2)
        new_ph = jnp.where(active, nph, ph)
        new_prev = jnp.where(active, ph, prev)
        fp2 = jnp.where(newly & is_cycle2, 1, fp2)
        fc = jnp.where(newly, t + 1, fc)
        fz = jnp.where(newly, 1, fz)
        t = jnp.where(active, t + 1, t)
        return new_ph, new_prev, t, sc, sd, cy, fz, fp2, fc

    init = (
        ph0,
        prev0,
        t_ref[...],
        settle_ref[...],
        settled_ref[...],
        cycled_ref[...],
        frozen_ref[...],
        frozen_p2_ref[...],
        freeze_ref[...],
    )
    ph, prev, t, sc, sd, cy, fz, fp2, fc = jax.lax.fori_loop(0, chunk, cycle, init)
    if packed:
        phase_out[...] = _pack_nibbles(ph)
        prev_out[...] = _pack_nibbles(prev)
    else:
        phase_out[...] = ph
        prev_out[...] = prev
    settle_out[...] = sc
    settled_out[...] = sd
    cycled_out[...] = cy
    frozen_out[...] = fz
    frozen_p2_out[...] = fp2
    freeze_out[...] = fc
    t_out[...] = t


def phase_step_multi_pallas(
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
    packed: bool = False,
    block_b: int = DEFAULT_BLOCK_B,
    interpret: bool = False,
):
    """Run ``chunk`` functional-mode cycles + settle/freeze bookkeeping in one
    launch; grid is 1-D over the batch (the weight matrix stays resident).

    ``phase``/``prev_phase``: (B, N) int32 counters — or (B, N/2) packed
    uint8 when ``packed`` (two counters per byte in the block-halves layout
    at width N, :func:`pack_block_halves`; unpacked in-register at launch
    and re-packed in the epilogue).  The seven bookkeeping columns are
    (B, 1) int32 (bools as {0, 1}).  Returns the 9-tuple
    (phase, prev_phase, settle_cycle, settled, cycled, frozen, frozen_p2,
    freeze_cycle, t) with the same shapes/dtypes as the inputs.
    """
    ni, nk = w.shape
    _require(ni == nk, f"phase_step_multi_pallas: weights {w.shape} not square")
    b = phase.shape[0]
    ph_cols = nk // 2 if packed else nk
    ph_dtype = jnp.uint8 if packed else jnp.int32
    if packed:
        _require(
            nk % PACKED_BLOCK_MULTIPLE == 0,
            f"phase_step_multi_pallas: packed N={nk} must be a multiple of "
            f"{PACKED_BLOCK_MULTIPLE}",
        )
    for name, arr in (("phase", phase), ("prev_phase", prev_phase)):
        _require(
            arr.shape == (b, ph_cols),
            f"phase_step_multi_pallas: {name} {arr.shape} != ({b}, {ph_cols})",
        )
    _require(bias.shape == (ni,), f"phase_step_multi_pallas: bias {bias.shape} != ({ni},)")
    flags = (t, settle_cycle, settled, cycled, frozen, frozen_p2, freeze_cycle)
    for arr in flags:
        _require(
            arr.shape == (b, 1),
            f"phase_step_multi_pallas: bookkeeping {arr.shape} != ({b}, 1)",
        )
    _require(
        b % block_b == 0,
        f"phase_step_multi_pallas: batch {b} not a multiple of block_b={block_b}",
    )
    _require(chunk >= 1, f"phase_step_multi_pallas: chunk must be >= 1, got {chunk}")
    grid = (b // block_b,)
    ph_spec = pl.BlockSpec((block_b, ph_cols), lambda bb: (bb, 0))
    flag_spec = pl.BlockSpec((block_b, 1), lambda bb: (bb, 0))
    flag_shape = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    return pl.pallas_call(
        functools.partial(_phase_step_multi_kernel, half, chunk, max_cycles, packed),
        grid=grid,
        in_specs=[
            pl.BlockSpec((ni, nk), lambda bb: (0, 0)),
            pl.BlockSpec((1, ni), lambda bb: (0, 0)),
            ph_spec,
            ph_spec,
            *([flag_spec] * 7),
        ],
        out_specs=[ph_spec, ph_spec, *([flag_spec] * 7)],
        out_shape=[
            jax.ShapeDtypeStruct((b, ph_cols), ph_dtype),
            jax.ShapeDtypeStruct((b, ph_cols), ph_dtype),
            *([flag_shape] * 7),
        ],
        interpret=interpret,
    )(
        w,
        bias.reshape(1, -1),
        phase,
        prev_phase,
        t,
        settle_cycle,
        settled,
        cycled,
        frozen,
        frozen_p2,
        freeze_cycle,
    )


# ---------------------------------------------------------------------------
# Hybrid serialized-MAC coupling: the paper's hybrid datapath as a sequence
# of blocked kernel launches.  The coupling sum is serialized into
# ceil(N / P) passes of P-wide MACs; passes are grouped so that each *pass-
# group* (as many passes as fill one hardware-aligned contraction block) is
# ONE kernel launch streaming its weight slice HBM→VMEM — the TPU image of
# the FPGA's fast-clock counter walking BRAM rows.  The int32 MAC
# accumulator is carried *between* launches (donated via
# input_output_aliases), and the final launch fuses the bias + phase-align
# epilogue.  Batch is a real grid dimension in every launch.
# ---------------------------------------------------------------------------


def hybrid_pass_groups(parallel: int, target_block_k: int = DEFAULT_BLOCK_K):
    """(passes_per_group, group width) for a serialized-MAC launch schedule.

    Each launch covers as many P-wide passes as fit the target contraction
    block; a P wider than the target runs one pass per launch.
    """
    if parallel <= 0:
        raise ValueError(f"parallel must be positive, got {parallel}")
    passes_per_group = max(1, target_block_k // parallel)
    return passes_per_group, passes_per_group * parallel


def _hybrid_mac_pass_kernel(sigma_ref, w_ref, acc_ref, out_ref):
    """One pass-group: out = acc + σ_g · W_gᵀ (exact int32 accumulation)."""
    out_ref[...] = acc_ref[...] + jax.lax.dot_general(
        sigma_ref[...],
        w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def _hybrid_phase_epilogue_kernel(
    half: int, sigma_ref, w_ref, acc_ref, bias_ref, phase_ref, out_ref
):
    """Final pass-group fused with the bias + phase-align epilogue."""
    s = (
        acc_ref[...]
        + jax.lax.dot_general(
            sigma_ref[...],
            w_ref[...],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        + bias_ref[...].astype(jnp.int32)
    )
    keep = phase_ref[...]
    out_ref[...] = jnp.where(
        s > 0, jnp.int32(0), jnp.where(s < 0, jnp.int32(half), keep)
    )


def _hybrid_launch_shapes(sigma, w, parallel, block_b, block_i, block_k):
    b, n = sigma.shape
    ni, nk = w.shape
    _require(n == nk, f"hybrid: sigma N={n} != weights N={nk}")
    _, width = hybrid_pass_groups(parallel, block_k)
    _require(
        b % block_b == 0 and ni % block_i == 0 and nk % width == 0,
        f"hybrid: shapes (b={b}, ni={ni}, nk={nk}) not multiples of "
        f"(block_b={block_b}, block_i={block_i}, pass-group width={width}); "
        "pad with pad_to_blocks",
    )
    return b, ni, nk, width


def _hybrid_pass_call(kernel, extra_specs, out_dtype, b, ni, width, block_b, block_i, interpret):
    grid = (ni // block_i, b // block_b)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, width), lambda i, bb: (bb, 0)),
            pl.BlockSpec((block_i, width), lambda i, bb: (i, 0)),
            pl.BlockSpec((block_b, block_i), lambda i, bb: (bb, i)),
            *extra_specs,
        ],
        out_specs=pl.BlockSpec((block_b, block_i), lambda i, bb: (bb, i)),
        out_shape=jax.ShapeDtypeStruct((b, ni), out_dtype),
        input_output_aliases={2: 0},  # the MAC accumulator is donated through
        interpret=interpret,
    )


def hybrid_coupling_sum_pallas(
    sigma: jax.Array,
    w: jax.Array,
    *,
    parallel: int,
    block_b: int = DEFAULT_BLOCK_B,
    block_i: int = DEFAULT_BLOCK_I,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """S[b,i] = Σ_j W[i,j] σ[b,j] through the serialized pass-group schedule.

    One kernel launch per pass-group (``hybrid_pass_groups``); the int32
    accumulator rides between launches.  Shapes must be pre-padded: batch to
    ``block_b``, rows to ``block_i``, columns to the pass-group width.
    """
    b, ni, nk, width = _hybrid_launch_shapes(sigma, w, parallel, block_b, block_i, block_k)
    acc = jnp.zeros((b, ni), jnp.int32)
    call = _hybrid_pass_call(
        _hybrid_mac_pass_kernel, [], jnp.int32, b, ni, width, block_b, block_i, interpret
    )
    for g in range(nk // width):
        sl = slice(g * width, (g + 1) * width)
        acc = call(sigma[:, sl], w[:, sl], acc)
    return acc


def hybrid_phase_step_pallas(
    sigma: jax.Array,
    w: jax.Array,
    bias: jax.Array,
    phase: jax.Array,
    *,
    half: int,
    parallel: int,
    block_b: int = DEFAULT_BLOCK_B,
    block_i: int = DEFAULT_BLOCK_I,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """Fused hybrid functional-mode cycle: serialized MAC pass-groups, then
    θ' = phase-align(S + h, θ) in the final launch's epilogue.

    Same contract as :func:`phase_step_pallas` (``phase`` int32 counters,
    S == 0 keeps the phase), but the contraction runs as one launch per
    pass-group with the accumulator carried between launches.
    """
    b, ni, nk, width = _hybrid_launch_shapes(sigma, w, parallel, block_b, block_i, block_k)
    _require(bias.shape == (ni,), f"hybrid_phase_step: bias {bias.shape} != ({ni},)")
    _require(
        phase.shape == (b, ni),
        f"hybrid_phase_step: phase {phase.shape} != ({b}, {ni})",
    )
    groups = nk // width
    acc = jnp.zeros((b, ni), jnp.int32)
    mac_call = _hybrid_pass_call(
        _hybrid_mac_pass_kernel, [], jnp.int32, b, ni, width, block_b, block_i, interpret
    )
    for g in range(groups - 1):
        sl = slice(g * width, (g + 1) * width)
        acc = mac_call(sigma[:, sl], w[:, sl], acc)
    epilogue_call = _hybrid_pass_call(
        functools.partial(_hybrid_phase_epilogue_kernel, half),
        [
            pl.BlockSpec((1, block_i), lambda i, bb: (0, i)),
            pl.BlockSpec((block_b, block_i), lambda i, bb: (bb, i)),
        ],
        jnp.int32,
        b,
        ni,
        width,
        block_b,
        block_i,
        interpret,
    )
    sl = slice((groups - 1) * width, groups * width)
    return epilogue_call(
        sigma[:, sl], w[:, sl], acc, bias.reshape(1, -1), phase.astype(jnp.int32)
    )


# ---------------------------------------------------------------------------
# quantized_matvec: the transferable version of the hybrid insight — a
# weight-streaming int8 GEMV with on-chip f32 accumulation and a per-row
# dequantization epilogue (memory-bound decode shapes).
# ---------------------------------------------------------------------------


def _quantized_matvec_kernel(x_ref, w_ref, scale_ref, out_ref, acc_ref):
    k = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...],
        w_ref[...].astype(jnp.float32),
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == nk - 1)
    def _epilogue():
        out_ref[...] = acc_ref[...] * scale_ref[...]


def quantized_matvec_pallas(
    x: jax.Array,
    w_q: jax.Array,
    scale: jax.Array,
    *,
    block_b: int = 8,
    block_m: int = DEFAULT_BLOCK_I,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """y[b,m] = Σ_k x[b,k] W_q[m,k] · scale[m]  (f32 out)."""
    b, kdim = x.shape
    m, kw = w_q.shape
    _require(kdim == kw, f"quantized_matvec_pallas: x K={kdim} != weights K={kw}")
    _require(
        b % block_b == 0 and m % block_m == 0 and kdim % block_k == 0,
        f"quantized_matvec_pallas: shapes (b={b}, m={m}, k={kdim}) not "
        f"multiples of blocks ({block_b}, {block_m}, {block_k}); pad with "
        "pad_to_blocks",
    )
    grid = (m // block_m, b // block_b, kdim // block_k)
    scale2d = scale.reshape(1, -1)
    return pl.pallas_call(
        _quantized_matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, block_k), lambda i, bb, k: (bb, k)),
            pl.BlockSpec((block_m, block_k), lambda i, bb, k: (i, k)),
            pl.BlockSpec((1, block_m), lambda i, bb, k: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_b, block_m), lambda i, bb, k: (bb, i)),
        out_shape=jax.ShapeDtypeStruct((b, m), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_b, block_m), jnp.float32)],
        interpret=interpret,
    )(x, w_q, scale2d)
