"""Real-width compiles of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each case compiles one kernel, at the block
shapes ``repro.kernels.autotune`` hands out for its bucket, for a v5e that
is described (``v5e:2x2``) and not attached.  The TPU compiler refuses what
the interpreter accepts — Mosaic layouts it cannot lower, tiles that
overrun VMEM — so these compiles guard the chip path at no chip time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune
from repro.kernels import coupling_kernel as k


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to a persistent cache but
    # cannot be read back without one; keep the cache out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _multi(n, b, packed):
    blocks = autotune.blocks_for("multi", n=n, batch=b)
    mult = k.PACKED_BLOCK_MULTIPLE if packed else 128
    n_pad = -(-n // mult) * mult
    ph = ((b, n_pad // 2), jnp.uint8) if packed else ((b, n_pad), jnp.int32)
    flag = ((b, 1), jnp.int32)
    fn = functools.partial(
        k.phase_step_multi_pallas, half=8, chunk=8, max_cycles=100, packed=packed,
        block_b=blocks.block_b, interpret=False,
    )
    return fn, [((n_pad, n_pad), jnp.int8), ((n_pad,), jnp.int32), ph, ph] + [flag] * 7


def _phase_step(n, b):
    bb, bi, bk = autotune.blocks_for("step", n=n, batch=b)
    fn = functools.partial(
        k.phase_step_pallas, half=8, block_b=bb, block_i=bi, block_k=bk, interpret=False
    )
    return fn, [((b, n), jnp.int8), ((n, n), jnp.int8), ((n,), jnp.int32), ((b, n), jnp.int32)]


def _phase_step_packed(n, b):
    bb, bi, bk = autotune.blocks_for("step", n=n, batch=b)
    blk = -(-max(bi, bk) // k.PACKED_BLOCK_MULTIPLE) * k.PACKED_BLOCK_MULTIPLE
    fn = functools.partial(
        k.phase_step_packed_pallas, half=8, block_b=bb, block_i=blk, block_k=blk,
        interpret=False,
    )
    return fn, [((b, n // 2), jnp.uint8), ((n, n), jnp.int8), ((n,), jnp.int32)]


def _coupling_sum(m, n, b):
    # The row-slab kernel: (M, N) coupling rows against (b, N) spins.
    bb, bi, bk = autotune.blocks_for("step", n=n, batch=b, m=m)
    m_pad, b_pad = -(-m // bi) * bi, -(-b // bb) * bb
    fn = functools.partial(
        k.coupling_sum_pallas, block_b=bb, block_i=bi, block_k=bk, interpret=False
    )
    return fn, [((b_pad, n), jnp.int8), ((m_pad, n), jnp.int8)]


def _ising_slab(n, replicas, groups):
    # One staggered update group: (window, N) coupling rows against the
    # (replicas, N) spins — the Max-Cut path's field evaluation.
    return _coupling_sum(-(-n // groups), n, replicas)


CASES = {
    "multi_n512_b128": lambda: _multi(512, 128, packed=False),
    "multi_n2048_b128": lambda: _multi(2048, 128, packed=False),
    "multi_packed_n512_b128": lambda: _multi(512, 128, packed=True),
    "phase_step_n4096_b128": lambda: _phase_step(4096, 128),
    "phase_step_packed_n2048_b128": lambda: _phase_step_packed(2048, 128),
    "ising_slab_n512_r8": lambda: _ising_slab(512, replicas=8, groups=16),
    # G1 Max-Cut: 16 groups of 64 rows of the 1024 bucket, 32 graphs x 8 replicas.
    "ising_slab_n1024_r256": lambda: _ising_slab(1024, replicas=256, groups=16),
    # One chip's row block of the N=131072 library sharded over four chips.
    "coupling_sum_n131072_m32768_b128": lambda: _coupling_sum(32768, 131072, 128),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    assert "tpu_custom_call" in compiled.as_text()
