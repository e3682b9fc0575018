"""The multi-cycle chunk kernel (``phase_step_multi_pallas``, launched by
``kernels/ops.py`` ``_phase_step_multi_jit``).

One launch runs ``settle_chunk`` cycles of the (B, N) phase state against
the resident (N, N) int8 W: each cycle is one (B, N) x (N, N) int8 product,
2·B·N² operations, whatever lanes have frozen.  It reads W, the bias, the
phase state and the bookkeeping columns once and writes the state back.
"""

from kernels._shapes import nbytes

MATCH = "_phase_step_multi_jit"


def cost(results, operands, cfg):
    """(int8 operations, bytes) of one launch from its HLO shapes."""
    (_, w), _, (_, phase) = operands[0], operands[1], operands[2]
    n = w[0]
    b = phase[0]
    return 2.0 * b * n * n * cfg["settle_chunk"], float(nbytes(operands) + nbytes(results))
