"""The row-slab coupling kernel (``coupling_sum_pallas``, launched by
``kernels/ops.py`` ``_coupling_sum_jit``): S[..., b, i] = Σ_j W[..., i, j] σ[..., b, j].

One launch reads σ (..., B, K) and W (..., M, K) int8 and writes S
(..., B, M) int32: 2·B·M·K int8 operations per leading index, and at least
every operand and result byte once.
"""

from math import prod

from kernels._shapes import nbytes

MATCH = "_coupling_sum_jit"


def cost(results, operands, cfg):
    """(int8 operations, bytes) of one launch from its HLO shapes."""
    (_, sigma), (_, w) = operands[0], operands[1]
    lead = prod(sigma[:-2])
    b, k = sigma[-2:]
    m = w[-2]
    return 2.0 * lead * b * m * k, float(nbytes(operands) + nbytes(results))
