"""Seeded inputs of the benchmark: couplings, probes and graphs.

Everything here is copied from the program's own generators rather than
imported, so that a change to the program cannot move the yardstick:

* ``random_patterns`` / ``corrupt_pool`` follow ``chip_smoke.py``'s seeded
  spins and ``retrieval_requests`` (stored ±1 patterns, a fraction of pixels
  flipped per probe);
* ``train_qat_doi`` is the Diederich-Opper I rule of ``repro.train.doi``
  with its 5-bit fake quantization (``repro.core.quantization``), written
  plainly for one library;
* ``random_graphs`` is ``repro.core.ising.random_graph`` (Erdos-Renyi upper
  triangle, symmetric, zero diagonal).

Every function takes its randomness from an explicit seed or key; the same
seed gives the same arrays.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

#: Matrix products of the set-up run in full float32.
_HIGHEST = jax.lax.Precision.HIGHEST


def qmax_of(bits: int) -> int:
    """Largest magnitude of a ``bits``-bit signed symmetric weight."""
    return (1 << (bits - 1)) - 1


def quantize(w: jax.Array, bits: int) -> jax.Array:
    """Symmetric round-to-nearest ``bits``-bit quantization, carried in int8.

    Scale ``max|w| / qmax`` (the paper's 5-bit weights use [-15, 15]).
    """
    qmax = qmax_of(bits)
    absmax = jnp.max(jnp.abs(w))
    scale = jnp.where(absmax > 0, absmax / qmax, jnp.float32(1.0))
    return jnp.clip(jnp.round(w / scale), -qmax, qmax).astype(jnp.int8)


def _fake_quantize(w: jax.Array, bits: int) -> jax.Array:
    qmax = qmax_of(bits)
    absmax = jnp.max(jnp.abs(w))
    scale = jnp.where(absmax > 0, absmax / qmax, jnp.float32(1.0))
    return jnp.clip(jnp.round(w / scale), -qmax, qmax) * scale


@partial(jax.jit, static_argnums=(1, 2))
def random_patterns(key: jax.Array, count: int, n: int) -> jax.Array:
    """(count, n) stored ±1 patterns, int8."""
    return jnp.where(jax.random.bernoulli(key, 0.5, (count, n)), 1, -1).astype(jnp.int8)


@partial(jax.jit, static_argnums=(2, 3))
def corrupt_pool(key, patterns, size: int, lanes: int, lo, hi):
    """(size, lanes, n) probes: a stored pattern with a fraction of pixels
    flipped, the fraction drawn uniformly in [lo, hi) per probe lane.
    Also returns the (size, lanes) index of the stored pattern."""
    k_which, k_frac, k_flip = jax.random.split(key, 3)
    count, n = patterns.shape
    which = jax.random.randint(k_which, (size, lanes), 0, count)
    frac = jax.random.uniform(k_frac, (size, lanes, 1), minval=lo, maxval=hi)
    flips = jax.random.uniform(k_flip, (size, lanes, n)) < frac
    clean = patterns[which]
    return jnp.where(flips, -clean, clean).astype(jnp.int8), which


@partial(jax.jit, static_argnums=(1, 2, 3))
def train_qat_doi(xi: jax.Array, bits: int, max_sweeps: int, threshold: float):
    """Quantization-aware Diederich-Opper I couplings for one library.

    ``xi``: (P, N) ±1.  Starts from the Hebbian matrix / N with a zero
    diagonal and sweeps the patterns in order; a pattern row whose margin
    ξ_i (W_q ξ)_i on the ``bits``-bit fake-quantized weights is below
    ``threshold`` gets the Hebbian increment ξ_i ξ / N.  Stops once a sweep
    changes nothing.  Returns (float weights, sweeps run, converged).
    """
    xi = xi.astype(jnp.float32)
    p, n = xi.shape
    lr = jnp.float32(1.0 / n)
    off_diag = 1.0 - jnp.eye(n, dtype=jnp.float32)
    w0 = jnp.matmul(xi.T, xi, precision=_HIGHEST) / n * off_diag

    def visit(w, pat):
        w_eff = _fake_quantize(w, bits) * off_diag
        kappa = pat * jnp.matmul(w_eff, pat, precision=_HIGHEST)
        unstable = (kappa < threshold).astype(jnp.float32)
        return w + lr * jnp.outer(unstable * pat, pat) * off_diag, jnp.sum(unstable)

    def body(carry):
        w, sweeps, _ = carry
        w, counts = jax.lax.scan(visit, w, xi)
        return w, sweeps + 1, jnp.sum(counts)

    def cond(carry):
        _, sweeps, unstable = carry
        return (unstable > 0) & (sweeps < max_sweeps)

    w, sweeps, unstable = jax.lax.while_loop(
        cond, body, (w0, jnp.int32(0), jnp.float32(1.0))
    )
    return w, sweeps, unstable == 0


@partial(jax.jit, static_argnums=(1, 2))
def random_graphs(keys: jax.Array, n: int, p: float) -> jax.Array:
    """(len(keys), n, n) Erdos-Renyi 0/1 adjacency, symmetric, zero diagonal."""

    def one(k):
        upper = jnp.triu(jax.random.bernoulli(k, p, (n, n)), k=1).astype(jnp.int8)
        return upper + upper.T

    return jax.vmap(one)(keys)

