"""Plain reference of ONN pattern retrieval (functional mode).

The synchronous dynamics of the paper's digital ONN, written from its
definition and nothing of the program: every oscillator holds a phase
counter θ ∈ [0, 2^phase_bits), its spin is +1 in the first half-period and
-1 in the second; each oscillation cycle every oscillator computes the
integer field S = W σ + b and snaps to phase 0 if S > 0, to the half period
if S < 0, and keeps its phase if S = 0.  Over ``max_cycles`` cycles a lane
records the first cycle in which no phase changed (``settle_cycle``,
``settled``) and whether it entered a period-2 orbit before settling
(``cycled``); its result is the phase after ``max_cycles`` cycles.

Once every lane is at a fixed point or in a period-2 orbit the remaining
cycles are known without computing them (the map is deterministic and
depends on the phases alone), so the loop stops there and a period-2 lane's
final phase follows from the parity of the cycles left.

``weight_bits`` below the configuration's own gives the control: the same
dynamics on couplings requantized to fewer bits.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

FIELDS = ("final_phase", "final_sigma", "settle_cycle", "settled", "cycled")


@partial(jax.jit, static_argnums=(5,))
def _cycle(w, bias, phase, prev, flags, half):
    """One cycle for all lanes; ``flags`` = (t, settle_cycle, settled, cycled,
    frozen, freeze_t)."""
    t, settle_cycle, settled, cycled, frozen, freeze_t = flags
    sigma = jnp.where(phase < half, 1, -1).astype(jnp.int8)
    field = jnp.dot(sigma, w.T, preferred_element_type=jnp.int32) + bias
    new = jnp.where(field > 0, 0, jnp.where(field < 0, half, phase)).astype(phase.dtype)
    unchanged = jnp.all(new == phase, axis=-1)
    p2 = jnp.all(new == prev, axis=-1) & ~unchanged & (t > 0)
    settle_cycle = jnp.where(unchanged & ~settled, t, settle_cycle)
    settled = settled | unchanged
    cycled = cycled | (p2 & ~settled)
    newly = ~frozen & (unchanged | p2)
    freeze_t = jnp.where(newly, t + 1, freeze_t)
    frozen = frozen | unchanged | p2
    return new, phase, (t + 1, settle_cycle, settled, cycled, frozen, freeze_t)


def solve(w, bias, probes, *, phase_bits: int, max_cycles: int):
    """Reference results of ``probes`` (B, N) ±1 int8 on couplings ``w``.

    Returns (dict of the five result fields as numpy, (B,) cycles each lane
    needed: up to its freeze, or ``max_cycles``).
    """
    half = 1 << (phase_bits - 1)
    b = probes.shape[0]
    phase = jnp.where(probes > 0, 0, half).astype(jnp.uint8)
    prev = phase
    flags = (
        jnp.int32(0),
        jnp.full((b,), max_cycles, jnp.int32),
        jnp.zeros((b,), bool),
        jnp.zeros((b,), bool),
        jnp.zeros((b,), bool),
        jnp.full((b,), max_cycles, jnp.int32),
    )
    t = 0
    while t < max_cycles:
        phase, prev, flags = _cycle(w, bias, phase, prev, flags, half)
        t += 1
        if bool(jnp.all(flags[4])):
            break
    # Lanes in a period-2 orbit alternate between `phase` and `prev`.
    left = max_cycles - t
    in_p2 = ~jnp.all(phase == prev, axis=-1)
    final = jnp.where((in_p2 & (left % 2 == 1))[:, None], prev, phase)
    out = {
        "final_phase": final,
        "final_sigma": jnp.where(final < half, 1, -1).astype(jnp.int8),
        "settle_cycle": flags[1],
        "settled": flags[2],
        "cycled": flags[3],
    }
    return jax.device_get(out), np.asarray(jax.device_get(flags[5]))
