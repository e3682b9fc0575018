"""Quantization substrate: n-bit signed weights, 4-bit phases, int4 packing.

The paper's design point is 5-bit signed coupling weights (stored in BRAM)
and 4-bit phase counters.  On TPU we carry 5-bit values in ``int8`` (the MXU
consumes int8 natively) and offer an int4 *packed* layout (two values/byte)
for studying the memory-bound regime — the TPU analogue of the paper's
"weights move from registers into addressable memory".
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

DEFAULT_WEIGHT_BITS = 5


@dataclasses.dataclass(frozen=True, eq=False)
class QuantizedWeights:
    """Symmetric-quantized integer weights plus dequantization scale."""

    values: jax.Array  # int8, in [-qmax, qmax]
    scale: jax.Array  # float32 scalar: w_float ≈ values * scale
    bits: int = DEFAULT_WEIGHT_BITS

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1

    def dequantize(self) -> jax.Array:
        return self.values.astype(jnp.float32) * self.scale


def symmetric_qmax(bits: int) -> int:
    """Largest representable magnitude for ``bits``-bit signed symmetric."""
    return (1 << (bits - 1)) - 1


def quantize_weights(w: jax.Array, bits: int = DEFAULT_WEIGHT_BITS) -> QuantizedWeights:
    """Symmetric round-to-nearest quantization to ``bits`` signed bits.

    Uses the symmetric range [-qmax, qmax] (the paper's 5-bit signed weights;
    -16 is unused to keep negation exact: q(-w) == -q(w)).
    """
    qmax = symmetric_qmax(bits)
    absmax = jnp.max(jnp.abs(w))
    # Guard the all-zero matrix; scale stays positive.
    scale = jnp.where(absmax > 0, absmax / qmax, jnp.float32(1.0))
    q = jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8)
    return QuantizedWeights(values=q, scale=scale.astype(jnp.float32), bits=bits)


def fake_quantize(w: jax.Array, bits: int = DEFAULT_WEIGHT_BITS) -> jax.Array:
    """Quantize-dequantize: the float weights the ``bits``-bit hardware runs.

    Bit-exact with ``quantize_weights(w, bits).dequantize()`` (same scale
    choice, same rounding), but jittable inside a training step: the
    quantization-aware DO-I trainer (:mod:`repro.train.doi`) measures its
    stability margins on this projection, so convergence means "stable on
    the weights the FPGA stores", not on the float shadow weights.
    """
    qmax = symmetric_qmax(bits)
    absmax = jnp.max(jnp.abs(w))
    scale = jnp.where(absmax > 0, absmax / qmax, jnp.float32(1.0)).astype(jnp.float32)
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


def quantize_phase(theta_continuous: jax.Array, phase_bits: int = 4) -> jax.Array:
    """Quantize a continuous phase in [0, 2π) to a ``phase_bits`` counter."""
    n = 1 << phase_bits
    idx = jnp.round(theta_continuous / (2 * jnp.pi) * n).astype(jnp.int32) % n
    return idx.astype(jnp.uint8)


def pack_int4(values: jax.Array) -> jax.Array:
    """Pack int8 values in [-8, 7] into bytes, two per byte (low nibble first).

    The last axis must be even.  Returns ``uint8`` with half the last-axis
    length.
    """
    if values.shape[-1] % 2 != 0:
        raise ValueError(f"last axis must be even, got {values.shape}")
    lo = values[..., 0::2].astype(jnp.int32) & 0xF
    hi = values[..., 1::2].astype(jnp.int32) & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_int4` (sign-extending each nibble)."""

    def _sext(nib: jax.Array) -> jax.Array:
        return jnp.where(nib >= 8, nib - 16, nib).astype(jnp.int8)

    lo = _sext(packed.astype(jnp.int32) & 0xF)
    hi = _sext((packed.astype(jnp.int32) >> 4) & 0xF)
    out = jnp.stack([lo, hi], axis=-1)
    return out.reshape(*packed.shape[:-1], packed.shape[-1] * 2)


def weight_memory_bits(n: int, bits: int = DEFAULT_WEIGHT_BITS) -> int:
    """Total coupling-weight memory in bits for an N-oscillator ONN (Table 1)."""
    return n * n * bits


def accumulator_bits(n: int, weight_bits: int = DEFAULT_WEIGHT_BITS) -> int:
    """Width needed to accumulate N signed ``weight_bits`` values exactly.

    |S| ≤ N · qmax, so the accumulator needs ⌈log2(N·qmax + 1)⌉ + 1 bits.
    This is the adder width of the paper's arithmetic circuits and the reason
    int32 accumulation is always exact for the sizes considered here.
    """
    qmax = symmetric_qmax(weight_bits)
    return int(jnp.ceil(jnp.log2(n * qmax + 1))) + 1


def check_weight_range(values: jax.Array, bits: int = DEFAULT_WEIGHT_BITS) -> jax.Array:
    """Return a bool scalar: all values representable in ``bits`` signed bits."""
    qmax = symmetric_qmax(bits)
    return jnp.all((values >= -qmax) & (values <= qmax))
