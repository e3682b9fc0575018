"""Plain reference of the oscillatory Ising machine's Max-Cut anneal.

Written from the anneal's definition and nothing of the program.  An
instance is a 0/1 adjacency A; its couplings are J = -A at the stated
weight precision (symmetric ``weight_bits`` quantization of -A, which is
-qmax·A).  Each of ``replicas`` replicas starts from spins drawn per vertex:
σ_i = -1 if U(fold_in(fold_in(k_init, r), i)) < 0.5 else +1, with
(k_init, k_anneal) = split(key).  Each sweep t ranks the vertices by
U(fold_in(fold_in(k_anneal, t), i)) (stable ascending) and cuts the ranks
into ``groups`` consecutive groups of ceil(N / groups); the groups fire in
order, and the members of a group all take the sign of their field
S = J σ at once (a zero field keeps the spin).  After every sweep each
replica's cut Σ_{i<j} A_ij (1 - σ_i σ_j) / 2 is compared with its best so
far, and a strictly larger cut replaces it.  The result is the best
replica's spins and cut, the best cut after each sweep, every replica's
best cut, and the sweeps run.

``cut_dtype`` is the type the cut is computed and carried in: the
configuration states float32 (its contraction at full precision).  The
control computes it in bfloat16, operands, products and the best cut per
replica alike, which rounds cuts past 256 to bfloat16's spacing.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

FIELDS = ("sigma", "cut_value", "trace", "replica_cuts", "sweeps_run")

def _uniforms(key, n):
    return jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(key, i)))(jnp.arange(n))


def _anneal(adj, key, *, replicas, sweeps, groups, qmax, cut_dtype):
    n = adj.shape[0]
    w = (-qmax * adj).astype(jnp.int8)
    a_tri = jnp.triu(adj.astype(cut_dtype), k=1)
    total = jnp.sum(a_tri, dtype=cut_dtype)

    def cuts(s):
        sf = s.astype(cut_dtype)
        pair = jnp.einsum("ri,ij,rj->r", sf, a_tri, sf, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=cut_dtype)
        return (0.5 * (total - pair)).astype(cut_dtype)

    k_init, k_anneal = jax.random.split(key)
    u0 = jax.vmap(lambda r: _uniforms(jax.random.fold_in(k_init, r), n))(jnp.arange(replicas))
    s0 = jnp.where(u0 < 0.5, -1, 1).astype(jnp.int8)
    size = -(-n // groups)

    def sweep(carry, t):
        s, best, best_cut = carry
        order = jnp.argsort(_uniforms(jax.random.fold_in(k_anneal, t), n), stable=True)
        rank = jnp.zeros((n,), jnp.int32).at[order].set(jnp.arange(n, dtype=jnp.int32))
        group_of = rank // size

        def fire(s, g):
            field = jnp.dot(s, w.T, preferred_element_type=jnp.int32)
            flipped = jnp.where(field > 0, 1, jnp.where(field < 0, -1, s)).astype(jnp.int8)
            return jnp.where((group_of == g)[None, :], flipped, s), None

        s, _ = jax.lax.scan(fire, s, jnp.arange(groups))
        cut = cuts(s)
        better = cut > best_cut
        best = jnp.where(better[:, None], s, best)
        best_cut = jnp.maximum(cut, best_cut)
        return (s, best, best_cut), jnp.max(best_cut)

    (_, best, best_cut), trace = jax.lax.scan(
        sweep, (s0, s0, cuts(s0)), jnp.arange(sweeps)
    )
    r = jnp.argmax(best_cut)
    return {
        "sigma": best[r],
        "cut_value": best_cut[r].astype(jnp.float32),
        "trace": trace.astype(jnp.float32),
        "replica_cuts": best_cut.astype(jnp.float32),
        "sweeps_run": jnp.int32(sweeps),
    }


@partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _anneal_batch(adjs, keys, replicas, sweeps, groups, qmax, cut_dtype):
    return jax.vmap(
        lambda a, k: _anneal(a, k, replicas=replicas, sweeps=sweeps, groups=groups,
                             qmax=qmax, cut_dtype=jnp.dtype(cut_dtype))
    )(adjs, keys)


def solve(adjs, keys, *, replicas, sweeps, groups, weight_bits, cut_dtype="float32"):
    """Reference results of instances ``adjs`` (B, N, N) under ``keys`` (B, 2)."""
    qmax = (1 << (weight_bits - 1)) - 1
    out = _anneal_batch(jnp.asarray(adjs), jnp.asarray(keys), replicas, sweeps, groups,
                        qmax, cut_dtype)
    return jax.device_get(out)
