"""The hybrid serialized-MAC backend and its satellites.

Acceptance surface of the cycle-faithful hybrid datapath: config semantics
of ``parallel_factor``, bit-exactness against the parallel backend across
MAC widths (ragged tails, P=N degeneracy) in both modes, masked-lane padding, the P-aware engine cost model and FPGA trade
quotes, the CLI plumbing, and the bench-regression gate's compare logic.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import coupling
from repro.core import dynamics


def _instance(seed, n, batch=4):
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.integers(-15, 16, (n, n)), jnp.int8)
    b = jnp.asarray(rng.integers(-3, 4, (n,)), jnp.int32)
    sigma0 = jnp.asarray(rng.choice([-1, 1], (batch, n)), jnp.int8)
    return w, b, sigma0


def _assert_results_equal(got, ref, msg=""):
    for field in ref._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(ref, field)),
            err_msg=f"{msg} field {field!r}",
        )


# ---------------------------------------------------------------------------
# Config semantics
# ---------------------------------------------------------------------------


def test_parallel_factor_selects_hybrid_backend():
    # parallel_factor no longer selects the backend on its own
    with pytest.raises(ValueError, match="parallel_factor"):
        dynamics.ONNConfig(n=16, parallel_factor=4)
    cfg = dynamics.ONNConfig(n=16, backend="hybrid", parallel_factor=4)
    assert cfg.backend == "hybrid"
    assert cfg.hybrid_parallel == 4
    assert cfg.hybrid_passes == 4


def test_parallel_factor_auto_and_clamp():
    # auto: DEFAULT_PARALLEL_FACTOR clamped to n
    assert dynamics.ONNConfig(n=8, backend="hybrid").hybrid_parallel == 8
    assert (
        dynamics.ONNConfig(n=256, backend="hybrid").hybrid_parallel
        == dynamics.DEFAULT_PARALLEL_FACTOR
    )
    # explicit P > n clamps to n (one pass)
    cfg = dynamics.ONNConfig(n=10, backend="hybrid", parallel_factor=64)
    assert cfg.hybrid_parallel == 10 and cfg.hybrid_passes == 1
    # ragged tail: 3 ∤ 10 → 4 passes
    assert dynamics.ONNConfig(n=10, backend="hybrid", parallel_factor=3).hybrid_passes == 4


def test_contradictory_route_flags_raise():
    with pytest.raises(ValueError, match="parallel_factor"):
        dynamics.ONNConfig(n=8, backend="pallas", parallel_factor=4)
    with pytest.raises(ValueError, match="parallel_factor"):
        dynamics.ONNConfig(n=8, backend="parallel", parallel_factor=4)
    with pytest.raises(ValueError, match="parallel_factor"):
        dynamics.ONNConfig(n=8, backend="hybrid", parallel_factor=-2)


def test_pad_config_freezes_the_resolved_mac_width():
    """Bucketing must not widen the datapath: an auto or clamped P resolved
    at the unpadded size stays the executed (and quoted) schedule."""
    cfg = dynamics.ONNConfig(n=20, backend="hybrid")  # auto → P=20
    padded = dynamics.pad_config(cfg, 32)
    assert cfg.hybrid_parallel == 20
    assert padded.hybrid_parallel == 20
    assert padded.hybrid_passes == 2  # ceil(32/20): idle passes, same lanes
    clamped = dynamics.ONNConfig(n=10, backend="hybrid", parallel_factor=64)
    assert dynamics.pad_config(clamped, 16).hybrid_parallel == 10


# ---------------------------------------------------------------------------
# Bit-exactness matrix: MAC widths × modes, vs parallel
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["functional", "rtl"])
@pytest.mark.parametrize("n,p", [(12, 1), (12, 5), (12, 12), (20, 7), (9, 2), (33, 32)])
def test_hybrid_bit_exact_with_parallel(n, p, mode):
    """Every tested (N, P): hybrid ≡ parallel on all result fields — P∤N
    ragged tails included, and P=N degenerating to the one-pass parallel
    schedule."""
    w, b, sigma0 = _instance(n * 13 + p, n)
    cfg_p = dynamics.ONNConfig(n=n, mode=mode, max_cycles=15, settle_chunk=4)
    cfg_h = dynamics.ONNConfig(
        n=n, mode=mode, backend="hybrid", parallel_factor=p,
        max_cycles=15, settle_chunk=4,
    )
    params = dynamics.make_params(cfg_p, w, b)
    ref = dynamics.retrieve(cfg_p, params, sigma0)
    got = dynamics.retrieve(cfg_h, params, sigma0)
    _assert_results_equal(got, ref, f"n={n} P={p} mode={mode}")


def test_hybrid_mac_sum_matches_parallel_sum():
    """The serialized-MAC sum the hybrid backend runs, over a sweep of
    widths."""
    w, _, sigma0 = _instance(3, 30)
    want = np.asarray(coupling.weighted_sum_parallel(w, sigma0))
    for p in (1, 2, 7, 16, 30):
        got = np.asarray(coupling.weighted_sum_serial(w, sigma0, chunk=p))
        np.testing.assert_array_equal(got, want, err_msg=f"P={p}")
    with pytest.raises(ValueError):
        coupling.weighted_sum_serial(w, sigma0, chunk=0)


def test_hybrid_padded_lanes_bit_exact():
    """Masked-lane padding (the engine bucket path) stays exact under the
    serialized schedule: zero columns only add idle MAC passes."""
    n, n_to = 11, 16
    w, b, sigma0 = _instance(29, n)
    cfg = dynamics.ONNConfig(
        n=n, backend="hybrid", parallel_factor=3, max_cycles=12, settle_chunk=3
    )
    params = dynamics.make_params(cfg, w, b)
    ref = dynamics.retrieve(cfg, params, sigma0)
    cfg_b = dynamics.pad_config(cfg, n_to)
    params_b = dynamics.pad_params(cfg, params, n_to)
    got = dynamics.retrieve(cfg_b, params_b, dynamics.pad_sigma(sigma0, n_to))
    np.testing.assert_array_equal(
        np.asarray(got.final_sigma[:, :n]), np.asarray(ref.final_sigma)
    )
    np.testing.assert_array_equal(np.asarray(got.settle_cycle), np.asarray(ref.settle_cycle))
    np.testing.assert_array_equal(np.asarray(got.settled), np.asarray(ref.settled))


def test_serialization_factor_is_parallel_aware():
    assert coupling.serialization_factor(506) == 508
    assert coupling.serialization_factor(506, parallel=8) == 66  # ceil(506/8)+2
    assert coupling.serialization_factor(506, parallel=506) == 3
    with pytest.raises(ValueError):
        coupling.serialization_factor(16, parallel=0)


# ---------------------------------------------------------------------------
# Engine: P-aware cost model and the per-request FPGA trade quote
# ---------------------------------------------------------------------------


def _hybrid_engine(n=20, p=8, max_cycles=40):
    from repro import engine as engine_lib

    rng = np.random.default_rng(7)
    xi = jnp.asarray(rng.choice([-1, 1], (3, n)), jnp.int8)
    solver = api.RetrievalSolver.from_patterns(
        xi, backend="hybrid", parallel_factor=p, max_cycles=max_cycles
    )
    eng = engine_lib.Engine(jax.random.PRNGKey(0), batch_buckets=(1, 2, 4))
    eng.install("letters", solver.as_engine_solver())
    return eng, xi


def test_engine_quotes_fpga_tradeoff():
    """Estimates carry the paper's per-design hardware quotes: recurrent,
    the paper's P=1 hybrid, and the configured P-wide hybrid."""
    eng, xi = _hybrid_engine(n=20, p=8)
    est = eng.estimate("letters", xi[:2])
    trade = est.fpga_tradeoff
    assert set(trade) == {"recurrent", "hybrid[P=1]", "hybrid[P=8]"}
    # at N=20 everything fits; wider MAC → faster hardware
    assert trade["hybrid[P=8]"] < trade["hybrid[P=1]"]
    assert est.fpga_seconds == pytest.approx(trade["hybrid[P=8]"])


def test_engine_hybrid_solver_serves_and_costs_the_schedule():
    """The hybrid adapter serves exactly like the parallel one and its cost
    units charge the full pass grid (idle ragged-tail lanes included)."""
    from repro import engine as engine_lib

    eng, xi = _hybrid_engine(n=20, p=8)
    adapter = eng.solver("letters")
    # Cold quotes charge worst-case max_cycles.  Bucket 32, P=8:
    # ceil(32/8)·8 = 32 → N² exactly; ragged bucket 20: ceil(20/8)·8 = 24 > 20
    # charges the idle tail MAC lanes.
    assert adapter.cost_units(32, 1) == pytest.approx(32 * 32 * 40)
    assert adapter.cost_units(20, 1) == pytest.approx(20 * 24 * 40)
    fut = eng.submit(engine_lib.Request("letters", xi))
    eng.drain()
    np.testing.assert_array_equal(np.asarray(fut.result().final_sigma), np.asarray(xi))
    # measured settle cycles tighten the quote, preserving the pass-grid shape
    tightened = adapter.cost_units(20, 1)
    assert tightened < 20 * 24 * 40
    assert tightened == pytest.approx(20 * 24 * adapter.expected_cycles())


def test_parallel_backend_has_no_configured_hybrid_quote():
    from repro import engine as engine_lib

    rng = np.random.default_rng(8)
    xi = jnp.asarray(rng.choice([-1, 1], (2, 16)), jnp.int8)
    solver = api.RetrievalSolver.from_patterns(xi, max_cycles=30)
    eng = engine_lib.Engine(jax.random.PRNGKey(0), batch_buckets=(1, 2))
    eng.install("l", solver.as_engine_solver())
    trade = eng.estimate("l", xi[:1]).fpga_tradeoff
    assert set(trade) == {"recurrent", "hybrid[P=1]"}


# ---------------------------------------------------------------------------
# CLI plumbing
# ---------------------------------------------------------------------------


def test_build_solver_hybrid_backend():
    from repro.launch.retrieve import build_solver

    solver, xi = build_solver("3x3", backend="hybrid", parallel_factor=4)
    assert solver.config.backend == "hybrid"
    assert solver.config.hybrid_parallel == 4
    out = solver.solve(xi)
    np.testing.assert_array_equal(np.asarray(out.final_sigma), np.asarray(xi))


# ---------------------------------------------------------------------------
# Bench-regression gate: compare logic + failure surfacing in benchmarks/run
# ---------------------------------------------------------------------------


def _payload(wall, cal):
    return {
        "bench": "dynamics",
        "smoke": True,
        "calibration_s": cal,
        "rows": [{"n": 48, "early_exit_s": wall, "fixed_scan_s": wall * 4, "vmap_run_s": wall * 5}],
    }


def test_check_regression_gates_on_normalized_wall_clock():
    from benchmarks import check_regression as cr

    base = cr._metrics("dynamics", _payload(0.01, 0.001))
    # same speed: passes
    ok, _ = cr.compare(base, cr._metrics("dynamics", _payload(0.01, 0.001)), 0.25)
    assert ok == []
    # 2× slower wall clock on the same machine: regression
    bad, _ = cr.compare(base, cr._metrics("dynamics", _payload(0.02, 0.001)), 0.25)
    assert len(bad) == 3
    # 2× slower wall clock on a 2× slower machine (calibration doubles): passes
    ok, _ = cr.compare(base, cr._metrics("dynamics", _payload(0.02, 0.002)), 0.25)
    assert ok == []


def test_check_regression_end_to_end_exit_codes(tmp_path):
    from benchmarks import check_regression as cr

    base_dir, fresh_dir = tmp_path / "base", tmp_path / "fresh"
    base_dir.mkdir()
    fresh_dir.mkdir()
    (base_dir / "BENCH_dynamics.json").write_text(json.dumps(_payload(0.01, 0.001)))
    (fresh_dir / "BENCH_dynamics.json").write_text(json.dumps(_payload(0.011, 0.001)))
    args = ["--baseline-dir", str(base_dir), "--fresh-dir", str(fresh_dir),
            "--benches", "dynamics", "--retries", "0"]
    assert cr.main(args) == 0
    (fresh_dir / "BENCH_dynamics.json").write_text(json.dumps(_payload(0.02, 0.001)))
    assert cr.main(args) == 1
    # missing baseline is a hard failure with its own exit code — "regenerate
    # the baseline" is a different fix than "chase a regression"
    (base_dir / "BENCH_dynamics.json").unlink()
    assert cr.main(args) == cr.EXIT_BASELINE
    # --update writes the fresh result as the new baseline
    assert cr.main(args + ["--update"]) == 0
    assert json.loads((base_dir / "BENCH_dynamics.json").read_text())["rows"]


def test_benchmarks_run_surfaces_section_failures():
    from benchmarks.run import run_sections

    calls = []

    def ok_section(**kw):
        calls.append("ok")

    def broken_section(**kw):
        raise RuntimeError("section exploded")

    failures = run_sections(
        [("good", ok_section, {}), ("bad", broken_section, {})]
    )
    assert calls == ["ok"]
    assert len(failures) == 1
    assert failures[0][0] == "bad"
    assert "exploded" in str(failures[0][1])
