"""Combinatorial-optimization embedding: ONNs as oscillatory Ising machines.

The paper motivates large all-to-all ONNs with problem embedding (max-cut,
graph coloring, SAT).  We implement max-cut: for a graph with adjacency A,
setting J = −A makes the Ising ground state the maximum cut, and the ONN's
phase dynamics search for it.

Two solvers share this module:

* :func:`solve_maxcut` — the sequential reference: each sweep visits every
  oscillator once in random order (``repro.core.dynamics.async_sweep``).
  Faithful to fully asynchronous hardware, but serial per oscillator — it
  is kept as the small-N oracle and the benchmark baseline.
* :func:`solve_maxcut_batch` — the batched, backend-native annealer.  A
  (replicas, N) spin state per instance advances through the *same*
  ``weighted_sum`` backend table as retrieval (``parallel`` / ``serial`` /
  ``pallas`` / ``hybrid`` with ``parallel_factor``), so Max-Cut runs on the
  serialized-MAC datapath, the fused Pallas kernels, and under
  ``constrain_onn`` sharding.  Asynchrony is modeled with **grouped
  staggered enables**: each sweep partitions the oscillators into K update
  groups (a fresh random partition per sweep, the hardware analogue of
  per-oscillator enable staggering); groups update sequentially, members of
  a group update together.  K = N recovers fully-asynchronous semantics
  (one oscillator per group), small K trades sweep serialization for
  backend-parallel work — the software face of the paper's
  parallelization/serialization trade.

Randomness is **counter-based per oscillator index** (``fold_in(key, i)``),
so the initial spins of oscillator ``i`` depend only on (key, replica, i)
and its per-sweep update group only on (key, sweep, i) — never on the
padded array size.  A
bucket-padded solve (zero-coupled extra vertices, masked out of every
group) is therefore *bit-identical* on the real vertices to the unpadded
solve, for any ``repro.engine`` bucket policy or occupancy.

``solve_maxcut_batch`` is exposed through ``repro.api.MaxCutSolver`` (the
same ``Solver`` protocol batched pattern retrieval implements), the
``repro.engine`` ``"maxcut"`` workload, and the ``repro.launch.maxcut``
CLI.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import dynamics
from repro.core.dynamics import ONNConfig, async_sweep, sign_update, weighted_sum
from repro.core.quantization import quantize_weights

#: Auto update-group count K for :func:`solve_maxcut_batch` when the caller
#: leaves ``stagger_groups`` 0: large enough that per-sweep serialization is
#: real, small enough that each group update is a wide backend contraction.
DEFAULT_STAGGER_GROUPS = 16


class MaxCutResult(NamedTuple):
    """Outcome of one max-cut anneal (batched: every field gains a leading
    instance dimension).

    ``sigma``/``cut_value`` are the best assignment seen across all sweeps
    and replicas; ``trace`` is the best-so-far cut after each sweep (tail
    entries repeat the final best when a solve exits early).  The batched
    solver also reports per-replica bests and the sweeps actually executed;
    the sequential reference leaves them ``None``.
    """

    sigma: jax.Array  # (..., N) best spin assignment (cut = partition by sign)
    cut_value: jax.Array  # (...,) number of cut edges (weighted)
    trace: jax.Array  # (..., sweeps) best cut value after each sweep
    replica_cuts: Optional[jax.Array] = None  # (..., replicas) best cut per replica
    sweeps_run: Optional[jax.Array] = None  # (...,) sweeps executed (early exit)


#: Cut values are float32 contractions of integer-valued spins and weights.
#: A TPU's default matmul precision rounds float32 operands to bfloat16,
#: which is inexact past 256 (an intermediate row sum at N=506), so the cut
#: contractions ask for full float32 precision.
_EXACT = jax.lax.Precision.HIGHEST


def maxcut_couplings(adjacency: jax.Array, weight_bits: int = 5):
    """Quantized ONN couplings for max-cut: J = −A (antiferromagnetic)."""
    return quantize_weights(-adjacency.astype(jnp.float32), bits=weight_bits)


def cut_value_exact(adjacency: jax.Array, sigma: jax.Array) -> jax.Array:
    """Weighted cut size Σ_{i<j} A_ij (1 − σ_i σ_j) / 2; ``sigma``: (..., N)."""
    sig = sigma.astype(jnp.float32)
    a = jnp.triu(adjacency.astype(jnp.float32), k=1)
    pair = jnp.einsum("...i,ij,...j->...", sig, a, sig, precision=_EXACT)
    total = jnp.sum(a)
    return 0.5 * (total - pair)


def resolve_stagger_groups(stagger_groups: int, n: int) -> int:
    """The effective update-group count K for an N-oscillator solve.

    0 resolves to ``min(DEFAULT_STAGGER_GROUPS, n)``; explicit values clamp
    to ``n`` (more groups than true vertices only adds empty groups, which
    is why the resolved K may differ across engine bucket sizes while the
    computed spins stay bit-identical).
    """
    if stagger_groups < 0:
        raise ValueError(f"stagger_groups must be >= 0, got {stagger_groups}")
    k = stagger_groups if stagger_groups > 0 else DEFAULT_STAGGER_GROUPS
    return max(1, min(k, n))


def _index_uniform(key: jax.Array, n: int) -> jax.Array:
    """(n,) uniforms u_i = U(fold_in(key, i)).

    Counter-based: the value at index ``i`` depends only on (key, i), not on
    ``n`` — the property that makes bucket-padded solves bit-identical to
    unpadded ones.
    """
    return jax.vmap(lambda i: jax.random.uniform(jax.random.fold_in(key, i)))(jnp.arange(n))


def _replica_index_uniform(key: jax.Array, replicas: int, n: int) -> jax.Array:
    """(replicas, n) counter-based uniforms, replica r drawing from
    ``fold_in(key, r)``."""
    return jax.vmap(lambda r: _index_uniform(jax.random.fold_in(key, r), n))(jnp.arange(replicas))


def staggered_sweep(
    cfg: ONNConfig,
    weights: jax.Array,
    sigma: jax.Array,
    key: jax.Array,
    *,
    groups: int,
    true_n: Optional[jax.Array] = None,
    frozen: Optional[jax.Array] = None,
) -> jax.Array:
    """One grouped-staggered-enable sweep of (replicas, N) spin states.

    A fresh random partition (counter-based priorities → rank order, shared
    by the replicas; their diversity comes from independent initial spins
    and divergent trajectories) chops the true vertices into ``groups``
    contiguous rank groups of ceil(true_n / groups).  Groups fire
    sequentially: each firing gathers its members' coupling rows and
    evaluates the integer field S = W[members] σ through ``cfg.backend`` —
    on hardware every enable window sees amplitudes from the state the
    previous group left behind — then sign-updates exactly those members.
    A full sweep therefore touches each coupling row once (the same N²
    MACs per replica as a sequential sweep), in K backend contractions
    instead of N serial row products.

    ``groups == N`` puts one oscillator per group — the asynchronous
    Hopfield sweep, which never increases the Ising energy; smaller K
    updates group members simultaneously — the serialization/parallelism
    trade of the paper, with the best-state bookkeeping in
    :func:`solve_maxcut_batch` absorbing any within-group oscillation.
    """
    n = cfg.n
    if true_n is None:
        true_n = jnp.int32(n)
    replicas = sigma.shape[0]
    u = _index_uniform(key, n)
    pri = jnp.where(jnp.arange(n) < true_n, u, jnp.inf)
    order = jnp.argsort(pri)  # rank → vertex; stable, padded vertices last
    group_size = jnp.maximum(1, (true_n + groups - 1) // groups)
    # Static slice window ≥ any true group's size; the window is anchored at
    # the group's first rank (clipped to stay in bounds) and over-covered
    # entries are masked, so padded solves replay unpadded ones bit-exactly.
    window = -(-n // groups)
    blocked = jnp.zeros((replicas,), bool) if frozen is None else frozen

    def fire(s: jax.Array, g: jax.Array):
        start = jnp.clip(g * group_size, 0, n - window)
        members = jax.lax.dynamic_slice(order, (start,), (window,))
        ranks = start + jnp.arange(window)
        field = weighted_sum(cfg, weights[members], s)  # (R, window)
        cur = s[:, members]
        mine = (ranks // group_size == g) & (ranks < true_n)
        upd = mine[None, :] & (~blocked)[:, None]
        merged = jnp.where(upd, sign_update(field, cur), cur)
        return s.at[:, members].set(merged), None

    sigma, _ = jax.lax.scan(fire, sigma, jnp.arange(groups))
    return sigma


class _AnnealCarry(NamedTuple):
    """While-loop carry of the batched annealer (one instance, R replicas)."""

    sigma: jax.Array  # (R, N) current spins
    best_sigma: jax.Array  # (R, N) best spins seen per replica
    best_cut: jax.Array  # (R,) best cut per replica
    since_improve: jax.Array  # (R,) sweeps since a replica last improved
    frozen: jax.Array  # (R,) replica stopped on cut-value stagnation
    trace: jax.Array  # (sweeps,) best-so-far cut across replicas
    ran: jax.Array  # () int32 sweeps actually executed
    t: jax.Array  # () int32 loop clock (may overrun `ran` by chunking)


def _solve_single(
    cfg: ONNConfig,
    adjacency: jax.Array,
    key: jax.Array,
    true_n: jax.Array,
    replicas: int,
    groups: int,
    stagnation: int,
) -> MaxCutResult:
    """Multi-replica anneal of one (padded) instance; shapes are static."""
    n, sweeps = cfg.n, cfg.max_cycles
    w = maxcut_couplings(adjacency, cfg.weight_bits).values
    valid = jnp.arange(n) < true_n
    a_tri = jnp.triu(adjacency.astype(jnp.float32), k=1)
    total_w = jnp.sum(a_tri)

    def cuts_of(sig: jax.Array) -> jax.Array:  # (R, N) -> (R,)
        s = sig.astype(jnp.float32)
        return 0.5 * (total_w - jnp.einsum("ri,ij,rj->r", s, a_tri, s, precision=_EXACT))

    k_init, k_anneal = jax.random.split(key)
    u0 = _replica_index_uniform(k_init, replicas, n)
    sigma0 = jnp.where(u0 < 0.5, -1, 1).astype(jnp.int8)
    cut0 = cuts_of(sigma0)

    def anneal_step(c: _AnnealCarry) -> _AnnealCarry:
        active = c.t < sweeps
        # `ran` counts sweeps until this instance's replicas all froze — NOT
        # loop iterations, which depend on sibling lanes under vmap (a
        # coalesced slab keeps iterating until every instance's cond drops,
        # and frozen instances' extra iterations are state no-ops).  Gating
        # on ~all(frozen) keeps sweeps_run invariant to bucket occupancy.
        running = active & ~jnp.all(c.frozen)
        sigma = staggered_sweep(
            cfg,
            w,
            c.sigma,
            jax.random.fold_in(k_anneal, c.t),
            groups=groups,
            true_n=true_n,
            frozen=c.frozen | ~active,
        )
        cut = cuts_of(sigma)
        improved = active & ~c.frozen & (cut > c.best_cut)
        best_sigma = jnp.where(improved[:, None], sigma, c.best_sigma)
        best_cut = jnp.maximum(cut, c.best_cut)
        since = jnp.where(improved, 0, c.since_improve + jnp.where(active, 1, 0))
        if stagnation > 0:
            frozen = c.frozen | (active & (since >= stagnation))
        else:
            frozen = c.frozen
        # mode="drop": the only out-of-range t values are inactive overrun
        # steps of the final chunk, which must not touch the trace.
        trace = c.trace.at[c.t].set(jnp.max(best_cut), mode="drop")
        return _AnnealCarry(
            sigma=sigma,
            best_sigma=best_sigma,
            best_cut=best_cut,
            since_improve=since,
            frozen=frozen,
            trace=trace,
            ran=c.ran + jnp.where(running, 1, 0),
            t=c.t + 1,
        )

    carry0 = _AnnealCarry(
        sigma=sigma0,
        best_sigma=sigma0,
        best_cut=cut0,
        since_improve=jnp.zeros((replicas,), jnp.int32),
        frozen=jnp.zeros((replicas,), bool),
        trace=jnp.zeros((sweeps,), jnp.float32),
        ran=jnp.int32(0),
        t=jnp.int32(0),
    )
    chunk = cfg.settle_chunk if cfg.settle_chunk > 0 else sweeps
    chunk = max(1, min(chunk, sweeps))

    def body(c: _AnnealCarry) -> _AnnealCarry:
        return jax.lax.fori_loop(0, chunk, lambda _, cc: anneal_step(cc), c)

    def cond(c: _AnnealCarry) -> jax.Array:
        return (c.t < sweeps) & ~jnp.all(c.frozen)

    final = jax.lax.while_loop(cond, body, carry0)
    best_overall = jnp.max(final.best_cut)
    trace = jnp.where(jnp.arange(sweeps) < final.ran, final.trace, best_overall)
    best_r = jnp.argmax(final.best_cut)
    return MaxCutResult(
        sigma=final.best_sigma[best_r],
        cut_value=final.best_cut[best_r],
        trace=trace,
        replica_cuts=final.best_cut,
        sweeps_run=final.ran,
    )


@partial(jax.jit, static_argnums=(0, 4, 5, 6, 7))
def _solve_maxcut_batch(
    cfg: ONNConfig,
    adjs: jax.Array,
    keys: jax.Array,
    true_n: jax.Array,
    replicas: int,
    groups: int,
    stagnation: int,
    _ctx=None,  # static sharding-context discriminator (see dynamics)
) -> MaxCutResult:
    dynamics.TRACE_COUNTER["solve_maxcut_batch"] += 1
    adjs = dynamics._shard_lanes(adjs)
    res = jax.vmap(
        lambda a, k, tn: _solve_single(cfg, a, k, tn, replicas, groups, stagnation)
    )(adjs, keys, true_n)
    return res._replace(sigma=dynamics._shard_lanes(res.sigma))


def solve_maxcut_batch(
    cfg: ONNConfig,
    adjacency: jax.Array,
    keys: jax.Array,
    *,
    replicas: int = 1,
    stagger_groups: int = 0,
    stagnation: int = 0,
    true_n: Optional[jax.Array] = None,
) -> MaxCutResult:
    """Anneal a batch of max-cut instances on the batched ONN core.

    ``adjacency``: (B, N, N) — or (N, N) for one instance, returning an
    unbatched result.  ``keys``: one PRNG key per instance, or a single key
    split per instance.  Each instance runs ``replicas`` independent anneals
    (fresh initial spins and sweep partitions per replica) of
    ``cfg.max_cycles`` grouped-staggered sweeps (:func:`staggered_sweep`,
    K = ``stagger_groups``; 0 → ``min(DEFAULT_STAGGER_GROUPS, N)``), with
    every field evaluation dispatched through ``cfg.backend`` — results are
    bit-exact across parallel/serial/pallas/hybrid for any
    ``parallel_factor``.

    ``stagnation`` > 0 enables per-replica early exit, mirroring
    ``run_batch``'s settle machinery: a replica freezes after that many
    sweeps without improving its best cut, the chunked while-loop
    (granularity ``cfg.settle_chunk``) stops once every replica of every
    instance is frozen, and ``trace`` repeats the final best over the
    un-run tail.

    ``true_n`` (B,) marks bucket-padded instances: vertices ≥ true_n are
    masked out of every update group and all randomness is counter-based
    per index, so a padded solve is bit-identical on the real vertices to
    the unpadded solve (not merely a valid anneal of the same instance).
    """
    adjacency = jnp.asarray(adjacency)
    single = adjacency.ndim == 2
    if single:
        adjacency = adjacency[None]
    if adjacency.ndim != 3 or adjacency.shape[-2:] != (cfg.n, cfg.n):
        raise ValueError(f"adjacency {adjacency.shape} != (B, {cfg.n}, {cfg.n})")
    b = adjacency.shape[0]
    if replicas < 1:
        raise ValueError(f"replicas must be >= 1, got {replicas}")
    if stagnation < 0:
        raise ValueError(f"stagnation must be >= 0, got {stagnation}")
    if keys is None:
        raise ValueError("solve_maxcut_batch requires PRNG keys")
    keys = jnp.asarray(keys)
    typed = jnp.issubdtype(keys.dtype, jax.dtypes.prng_key)
    if keys.ndim == (0 if typed else 1):
        # One key, one instance: use it directly, so the engine path (one
        # engine-split key per request lane) replays the direct API call
        # bit for bit.  One key, many instances: split per instance.
        keys = keys[None] if b == 1 else jax.random.split(keys, b)
    if true_n is None:
        true_n = jnp.full((b,), cfg.n, jnp.int32)
    else:
        true_n = jnp.asarray(true_n, jnp.int32)
        if true_n.ndim == 0:
            true_n = jnp.full((b,), true_n, jnp.int32)
    groups = resolve_stagger_groups(stagger_groups, cfg.n)
    res = _solve_maxcut_batch(
        cfg,
        adjacency,
        keys,
        true_n,
        replicas,
        groups,
        stagnation,
        dynamics._sharding_cache_key(),
    )
    if single:
        res = jax.tree.map(lambda x: x[0], res)
    return res


def solve_maxcut(
    adjacency: jax.Array,
    key: jax.Array,
    sweeps: int = 64,
    weight_bits: int = 5,
) -> MaxCutResult:
    """Sequential-sweep reference annealer (the pre-batched solver).

    Each sweep visits every oscillator once in a random order through
    ``async_sweep`` — serial per oscillator, so it does not scale, but it is
    the oracle the batched solver's K = N semantics mirror and the baseline
    ``benchmarks/maxcut.py`` measures against.  Use
    :func:`solve_maxcut_batch` (or ``repro.api.MaxCutSolver``) for anything
    performance-sensitive.
    """
    n = adjacency.shape[0]
    q = maxcut_couplings(adjacency, weight_bits)
    w = q.values
    k0, k1 = jax.random.split(key)
    sigma0 = jax.random.choice(k0, jnp.array([-1, 1], jnp.int8), shape=(n,))

    def body(carry, k):
        sigma, best_sigma, best_cut = carry
        order = jax.random.permutation(k, n)
        sigma = async_sweep(w, sigma, order)
        c = cut_value_exact(adjacency, sigma)
        better = c > best_cut
        best_sigma = jnp.where(better, sigma, best_sigma)
        best_cut = jnp.maximum(c, best_cut)
        return (sigma, best_sigma, best_cut), best_cut

    keys = jax.random.split(k1, sweeps)
    (_, best_sigma, best_cut), trace = jax.lax.scan(
        body, (sigma0, sigma0, cut_value_exact(adjacency, sigma0)), keys
    )
    return MaxCutResult(sigma=best_sigma, cut_value=best_cut, trace=trace)


def random_graph(key: jax.Array, n: int, p: float = 0.5) -> jax.Array:
    """Erdős–Rényi adjacency matrix (symmetric, zero diagonal, 0/1)."""
    upper = jax.random.bernoulli(key, p, (n, n))
    upper = jnp.triu(upper, k=1).astype(jnp.int8)
    return upper + upper.T
