"""repro.train: batched QAT DO-I trainer + ONN checkpoint round trips."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import train
from repro.checkpoint import load_onn, save_onn
from repro.core import dynamics, learning, quantization
from repro.train import doi


def _patterns(seed: int, p: int, n: int) -> jax.Array:
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.choice([-1, 1], (p, n)), jnp.int8)


# ---------------------------------------------------------------------------
# Trainer semantics
# ---------------------------------------------------------------------------


def test_train_converges_and_margins_hold():
    xi = _patterns(0, 6, 32)
    res = train.train_doi(xi, train.TrainConfig(threshold=1.0))
    assert bool(res.converged)
    assert int(res.sweeps) >= 1
    margins = learning.stability_margins(
        res.weights * (1.0 - jnp.eye(32)), xi
    )
    assert float(jnp.min(margins)) >= 1.0
    assert float(res.kappa_min) == pytest.approx(float(jnp.min(margins)), rel=1e-5)


def test_masked_padding_matches_sliced_library():
    """Trailing masked rows must be invisible: training a padded (P_max, N)
    library with n_patterns=k is bit-exact with training xi[:k]."""
    xi = _patterns(1, 8, 24)
    cfg = train.TrainConfig()
    full = train.train_doi(xi[:5], cfg)
    masked = train.train_doi(xi, cfg, n_patterns=5)
    np.testing.assert_array_equal(np.asarray(full.weights), np.asarray(masked.weights))
    assert int(full.sweeps) == int(masked.sweeps)
    assert float(full.kappa_min) == float(masked.kappa_min)


def test_vmapped_libraries_train_independently():
    """A (L, P, N) batch trains every library to the same *semantics* as a
    solo call — converged, margins clear threshold on its own live patterns,
    masked counts respected — and identical libraries inside one batch come
    out bit-identical (the done-freeze keeps finished libraries untouched
    while stragglers keep sweeping).  Bit-exactness *across* the solo/vmap
    paths is not asserted: batched matmuls reduce in a different order.
    """
    libs = jnp.stack([_patterns(s, 6, 20) for s in range(3)] + [_patterns(0, 6, 20)])
    counts = jnp.asarray([6, 4, 2, 6], jnp.int32)
    cfg = train.TrainConfig()
    batched = train.train_doi(libs, cfg, n_patterns=counts)
    assert bool(jnp.all(batched.converged))
    # Libraries 0 and 3 are the same data with the same count: bit-identical.
    np.testing.assert_array_equal(
        np.asarray(batched.weights[0]), np.asarray(batched.weights[3])
    )
    assert int(batched.sweeps[0]) == int(batched.sweeps[3])
    for i in range(3):
        solo = train.train_doi(libs[i], cfg, n_patterns=counts[i])
        assert bool(batched.converged[i]) == bool(solo.converged)
        live = libs[i][: int(counts[i])]
        margins = learning.stability_margins(
            batched.weights[i] * (1.0 - jnp.eye(20)), live
        )
        assert float(jnp.min(margins)) >= 1.0 - 1e-5


def test_lr_and_pattern_count_are_traced_operands():
    """One executable per (config, shape): changing lr or n_patterns — or
    calling at a different N where the lr=None default differs — never
    reuses a stale baked-in step size and never retraces for traced args."""
    xi = _patterns(2, 5, 28)
    cfg = train.TrainConfig()
    train.train_doi(xi, cfg)  # ensure traced
    before = dict(doi.TRACE_COUNTER)
    a = train.train_doi(xi, cfg, lr=0.05)
    b = train.train_doi(xi, cfg, lr=0.25, n_patterns=3)
    assert dict(doi.TRACE_COUNTER) == before, "traced operand caused a retrace"
    assert not np.array_equal(np.asarray(a.weights), np.asarray(b.weights))

    # lr=None must mean 1/N *of this call*, not of whichever call traced.
    small = _patterns(3, 4, 14)
    default = train.train_doi(small, cfg)
    explicit = train.train_doi(small, cfg, lr=1.0 / 14)
    np.testing.assert_array_equal(
        np.asarray(default.weights), np.asarray(explicit.weights)
    )


def test_qat_margins_survive_quantization():
    """QAT convergence is measured on the 5-bit projection, so the quantized
    network really holds the patterns: every pattern is a strict fixed point
    of the int8 sign dynamics and the dequantized margins clear threshold."""
    xi = _patterns(4, 10, 40)
    res = train.train_doi(xi, train.TrainConfig(qat_bits=5))
    assert bool(res.converged)
    qw = quantization.quantize_weights(res.weights, 5)
    assert bool(learning.patterns_are_fixed_points(qw.values, xi))
    margins = learning.stability_margins(qw.dequantize(), xi)
    assert float(jnp.min(margins)) >= 1.0 - 1e-5


def test_fake_quantize_matches_quantize_dequantize():
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=(30, 30)), jnp.float32)
    for bits in (4, 5, 8):
        fq = quantization.fake_quantize(w, bits)
        qdq = quantization.quantize_weights(w, bits).dequantize()
        np.testing.assert_array_equal(np.asarray(fq), np.asarray(qdq))


def test_self_coupling_off_masks_stability_check():
    """With self_coupling=False the κ check must not credit a diagonal term:
    a Hebbian-with-diagonal init would otherwise look converged while the
    stored (diagonal-free) couplings are not."""
    xi = _patterns(6, 8, 24)
    res = train.train_doi(xi, train.TrainConfig(self_coupling=False))
    assert bool(res.converged)
    np.testing.assert_array_equal(
        np.asarray(jnp.diagonal(res.weights)), np.zeros(24, np.float32)
    )
    masked = learning.stability_margins(res.weights * (1.0 - jnp.eye(24)), xi)
    # Recomputed outside the trainer, so float32 rounding may differ.
    assert float(jnp.min(masked)) >= 1.0 - 1e-6


def test_train_config_validation():
    with pytest.raises(ValueError, match="threshold"):
        train.TrainConfig(threshold=0.0)
    with pytest.raises(ValueError, match="max_sweeps"):
        train.TrainConfig(max_sweeps=0)
    with pytest.raises(ValueError, match="qat_bits"):
        train.TrainConfig(qat_bits=1)
    with pytest.raises(ValueError, match="xi"):
        train.train_doi(jnp.zeros((4,)))
    with pytest.raises(ValueError, match="n_patterns"):
        train.train_doi(_patterns(0, 4, 10), n_patterns=jnp.asarray([2, 2]))


def test_legacy_wrapper_defaults_resolve_per_call():
    """core.learning.diederich_opper_i delegates to the batched trainer and
    keeps its contract: converged weights whose margins clear threshold."""
    xi = _patterns(7, 4, 16)
    res = learning.diederich_opper_i(xi, self_coupling=False)
    assert bool(res.converged)
    margins = learning.stability_margins(res.weights, xi)
    assert float(jnp.min(margins)) >= 1.0


def test_trained_params_projects_to_serving_format():
    xi = _patterns(8, 4, 16)
    res = train.train_doi(xi, train.TrainConfig(qat_bits=5))
    cfg = dynamics.ONNConfig(n=16)
    params, qw = train.trained_params(cfg, res.weights)
    assert params.weights.dtype == jnp.int8
    assert qw.bits == cfg.weight_bits
    np.testing.assert_array_equal(np.asarray(params.weights), np.asarray(qw.values))
    with pytest.raises(ValueError, match="weights"):
        train.trained_params(dynamics.ONNConfig(n=8), res.weights)


# ---------------------------------------------------------------------------
# ONN checkpoints
# ---------------------------------------------------------------------------


def test_onn_checkpoint_round_trip(tmp_path):
    xi = _patterns(9, 5, 20)
    res = train.train_doi(xi, train.TrainConfig(qat_bits=5))
    cfg = dynamics.ONNConfig(n=20, max_cycles=64)
    params, qw = train.trained_params(cfg, res.weights)
    path = save_onn(
        str(tmp_path / "ckpt"), cfg, qw, params.bias, extra_meta={"sweeps": 7}
    )
    ck = load_onn(path)
    assert ck.config == cfg
    assert ck.meta == {"sweeps": 7}
    assert ck.quantized.bits == qw.bits
    np.testing.assert_array_equal(np.asarray(ck.quantized.values), np.asarray(qw.values))
    np.testing.assert_array_equal(
        np.asarray(ck.quantized.scale), np.asarray(qw.scale)
    )
    np.testing.assert_array_equal(np.asarray(ck.params.bias), np.asarray(params.bias))


def test_onn_checkpoint_overwrite_and_validation(tmp_path):
    cfg = dynamics.ONNConfig(n=12)
    xi = _patterns(10, 3, 12)
    _, qw = train.trained_params(cfg, train.train_doi(xi).weights)
    path = str(tmp_path / "ckpt")
    save_onn(path, cfg, qw)
    save_onn(path, cfg, qw, extra_meta={"v": 2})  # overwrite is atomic
    assert load_onn(path).meta == {"v": 2}
    with pytest.raises(ValueError, match="bit"):
        save_onn(path, dataclasses.replace(cfg, weight_bits=4), qw)
    with pytest.raises(ValueError, match="weights"):
        save_onn(path, dynamics.ONNConfig(n=8), qw)
