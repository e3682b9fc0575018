"""Byte sizes of the HLO element types the kernels use."""

from math import prod

_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
          "s32": 4, "u32": 4, "f32": 4}


def nbytes(types):
    """Total bytes of a list of (dtype, dims)."""
    return sum(_BYTES[d] * prod(dims) for d, dims in types)
