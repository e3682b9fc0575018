"""Pallas kernel validation: shape/dtype sweeps vs the ref.py oracles.

All kernels run in interpret mode on CPU (the TPU is the *target*); integer
kernels must be bit-exact, the f32 GEMV matches to blocked-accumulation
tolerance.
"""

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # optional dep (see pyproject.toml): skip, not fail
    from hypothesis_fallback import given, settings, st

from repro.core import coupling
from repro.kernels import coupling_kernel as kk
from repro.kernels import ops, ref

SHAPES_BN = [
    (1, 9),  # smallest paper dataset (3×3)
    (4, 48),  # recurrent-arch max capacity
    (8, 128),  # one exact block
    (3, 506),  # hybrid-arch max capacity (padding exercised)
    (16, 512),  # multi-block contraction
    (100, 484),  # 22×22 benchmark shape
    (257, 130),  # off-alignment both dims
]


@pytest.mark.parametrize("b,n", SHAPES_BN)
def test_coupling_sum_matches_ref(b, n):
    rng = np.random.default_rng(b * 1000 + n)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
    got = ops.coupling_sum(w, sig)
    want = ref.coupling_sum_ref(w, sig)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,n", SHAPES_BN)
def test_onn_step_matches_ref(b, n):
    rng = np.random.default_rng(b * 7 + n)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
    bias = jnp.asarray(rng.integers(-10, 11, (n,)), jnp.int32)
    got = ops.onn_step(w, sig, bias)
    want = ref.onn_step_ref(w, sig, bias)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_onn_step_tie_keeps_spin():
    """S == 0 must keep the current spin (the paper's zero-sum rule)."""
    n = 16
    w = jnp.zeros((n, n), jnp.int8)
    sig = jnp.asarray(np.random.default_rng(0).choice([-1, 1], (4, n)), jnp.int8)
    out = ops.onn_step(w, sig)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(sig))


@pytest.mark.parametrize(
    "block_b,block_i,block_k", [(8, 128, 128), (16, 256, 64), (128, 128, 512)]
)
def test_coupling_sum_block_shape_sweep(block_b, block_i, block_k):
    """Block shape never changes the integer result (schedule invariance —
    the TPU restatement of the paper's serialization-equivalence claim)."""
    rng = np.random.default_rng(42)
    b, n = 64, 512
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
    got = ops.coupling_sum(w, sig, block_b=block_b, block_i=block_i, block_k=block_k)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.coupling_sum_ref(w, sig))
    )


def test_coupling_sum_1d_input():
    rng = np.random.default_rng(1)
    n = 100
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (n,)), jnp.int8)
    got = ops.coupling_sum(w, sig)
    assert got.shape == (n,)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.coupling_sum_ref(w, sig[None, :])[0])
    )


@settings(max_examples=20, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    b=st.sampled_from([1, 2, 8, 33]),
    n=st.sampled_from([9, 20, 42, 129]),
)
def test_property_kernel_exactness(seed, b, n):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(ops.coupling_sum(w, sig)),
        np.asarray(ref.coupling_sum_ref(w, sig)),
    )
    np.testing.assert_array_equal(
        np.asarray(ops.onn_step(w, sig)),
        np.asarray(ref.onn_step_ref(w, sig)),
    )


# ---------------------------------------------------------------------------
# The production kernels against the serialized-MAC schedule and the oracles
# ---------------------------------------------------------------------------

SERIAL_CASES = [
    # (batch, n, parallel): P=1 single-MAC, ragged P∤N, P=N one pass,
    # P wider than a 128 tile, multi-block shapes.
    (3, 9, 1),
    (4, 20, 7),
    (2, 48, 48),
    (5, 130, 32),
    (8, 257, 200),
    (3, 506, 8),
]


@pytest.mark.parametrize("b,n,parallel", SERIAL_CASES)
def test_coupling_sum_matches_serialized_oracle(b, n, parallel):
    """The kernel's blocked sum equals the paper's serialized-MAC schedule
    (``ceil(N / P)`` P-wide passes) and the one-shot oracle."""
    rng = np.random.default_rng(b * 1000 + n + parallel)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
    got = np.asarray(ops.coupling_sum(w, sig))
    np.testing.assert_array_equal(
        got, np.asarray(coupling.weighted_sum_serial(w, sig, chunk=parallel))
    )
    np.testing.assert_array_equal(got, np.asarray(ref.coupling_sum_ref(w, sig)))


@pytest.mark.parametrize("b,n", [(b, n) for b, n, _ in SERIAL_CASES])
def test_phase_step_matches_ref(b, n):
    rng = np.random.default_rng(b * 77 + n)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    sig = jnp.asarray(rng.choice([-1, 1], (b, n)), jnp.int8)
    bias = jnp.asarray(rng.integers(-10, 11, (n,)), jnp.int32)
    phase = jnp.asarray(rng.integers(0, 16, (b, n)), jnp.uint8)
    got = ops.phase_step(w, sig, bias, phase, half=8)
    want = ref.phase_step_ref(w, sig, bias, phase.astype(jnp.int32), 8)
    assert got.dtype == phase.dtype
    np.testing.assert_array_equal(np.asarray(got).astype(np.int32), np.asarray(want))


def test_phase_step_tie_keeps_phase():
    """S == 0 must keep the current (possibly non-canonical) phase counter."""
    n = 24
    w = jnp.zeros((n, n), jnp.int8)
    rng = np.random.default_rng(0)
    sig = jnp.asarray(rng.choice([-1, 1], (4, n)), jnp.int8)
    phase = jnp.asarray(rng.integers(0, 16, (4, n)), jnp.int32)
    out = ops.phase_step(w, sig, None, phase, half=8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(phase))


@pytest.mark.parametrize(
    "b,m,k", [(1, 256, 512), (4, 100, 300), (8, 512, 1024), (2, 384, 640)]
)
def test_quantized_matvec_matches_ref(b, m, k):
    rng = np.random.default_rng(m + k)
    wq = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
    scale = jnp.asarray(rng.random((m,)) * 0.01 + 1e-4, jnp.float32)
    x = jnp.asarray(rng.standard_normal((b, k)), jnp.float32)
    got = np.asarray(ops.quantized_matvec(wq, scale, x))
    want = np.asarray(ref.quantized_matvec_ref(wq, scale, x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_quantized_matvec_scalar_scale():
    rng = np.random.default_rng(3)
    wq = jnp.asarray(rng.integers(-127, 128, (128, 256)), jnp.int8)
    x = jnp.asarray(rng.standard_normal((4, 256)), jnp.float32)
    got = np.asarray(ops.quantized_matvec(wq, jnp.float32(0.5), x))
    want = np.asarray(ref.quantized_matvec_ref(wq, jnp.full((128,), 0.5, jnp.float32), x))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_vmem_budget_of_default_blocks():
    """Default block shapes keep the fused working set well inside VMEM."""
    budget = 16 * 1024 * 1024  # v5e ~16 MiB VMEM/core
    assert kk.vmem_bytes(kk.DEFAULT_BLOCK_B, kk.DEFAULT_BLOCK_I, kk.DEFAULT_BLOCK_K) < budget // 4
