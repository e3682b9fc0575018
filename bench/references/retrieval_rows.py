"""Plain reference of ONN pattern retrieval with W held in row blocks.

The synchronous dynamics of ``references/retrieval.py`` (phase counters,
spin +1 in the first half-period, snap to 0 or the half period on the sign
of the field S = W σ + b, keep the phase on S = 0; the same flags, the same
early stop once every lane is at a fixed point or in a period-2 orbit, the
same five result fields), for a W too large for one chip.  W arrives as a
``NamedSharding`` whose first dimension is split over one mesh axis: each
device computes the field of its own rows with a plain int8 dot against the
full spin vector and snaps those oscillators' phases, and the new phases are
gathered to every device for the next cycle.  (The program under test
combines partial fields with a psum instead; this reference gathers phases.)

Imports nothing of the program.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

FIELDS = ("final_phase", "final_sigma", "settle_cycle", "settled", "cycled")


@partial(jax.jit, static_argnums=(5, 6, 7))
def _cycle(w, bias, phase, prev, flags, half, mesh, axis):
    """One cycle for all lanes; ``flags`` = (t, settle_cycle, settled, cycled,
    frozen, freeze_t)."""
    t, settle_cycle, settled, cycled, frozen, freeze_t = flags

    def own_rows(wb, bb, ph):
        rows = wb.shape[0]
        start = jax.lax.axis_index(axis) * rows
        sigma = jnp.where(ph < half, 1, -1).astype(jnp.int8)
        field = jnp.dot(sigma, wb.T, preferred_element_type=jnp.int32) + bb
        mine = jax.lax.dynamic_slice_in_dim(ph, start, rows, axis=1)
        new = jnp.where(field > 0, 0, jnp.where(field < 0, half, mine)).astype(ph.dtype)
        return jax.lax.all_gather(new, axis, axis=1, tiled=True)

    new = jax.shard_map(own_rows, mesh=mesh, in_specs=(P(axis, None), P(axis), P()),
                        out_specs=P(), check_vma=False)(w, bias, phase)
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
    """Reference results of ``probes`` (B, N) ±1 int8 on row-sharded couplings
    ``w`` and bias ``bias`` (N,) int32.

    Returns (dict of the five result fields as numpy, (B,) cycles each lane
    needed: up to its freeze, or ``max_cycles``).
    """
    mesh, axis = w.sharding.mesh, w.sharding.spec[0]
    everywhere = NamedSharding(mesh, P())
    half = 1 << (phase_bits - 1)
    b = probes.shape[0]
    phase = jax.device_put(np.where(np.asarray(probes) > 0, 0, half).astype(np.uint8), everywhere)
    bias = jax.device_put(bias, everywhere)
    prev = phase
    flags = jax.device_put((
        np.int32(0),
        np.full((b,), max_cycles, np.int32),
        np.zeros((b,), bool),
        np.zeros((b,), bool),
        np.zeros((b,), bool),
        np.full((b,), max_cycles, np.int32),
    ), everywhere)
    t = 0
    while t < max_cycles:
        phase, prev, flags = _cycle(w, bias, phase, prev, flags, half, mesh, axis)
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
