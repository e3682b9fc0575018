"""End-to-end launcher tests: train loop (checkpoint/restart), serving loop,
ONN retrieval service, engine-served max-cut, and the process and
compile-cache rules of the entry points."""

import os
import shutil
import subprocess
import sys

import pytest

from repro.api import MaxCutSolver
from repro.launch.maxcut import serve_cuts
from repro.launch.retrieve import build_solver, serve_requests
from repro.launch.serve import serve
from repro.launch.train import train


def test_train_loop_loss_decreases(tmp_path):
    out = train(
        "qwen2-1.5b", reduced=True, steps=30, batch=4, seq_len=64,
        ckpt_dir=str(tmp_path), ckpt_every=10, log_every=0, lr=1e-3,
    )
    assert out["status"] == "completed"
    assert out["final_step"] == 30
    assert out["last_loss"] < out["first_loss"], (
        f"loss did not decrease: {out['first_loss']} → {out['last_loss']}"
    )


def test_train_resume_continues(tmp_path):
    d = str(tmp_path)
    train("qwen2-1.5b", reduced=True, steps=10, batch=4, seq_len=64,
          ckpt_dir=d, ckpt_every=5, log_every=0)
    out2 = train("qwen2-1.5b", reduced=True, steps=20, batch=4, seq_len=64,
                 ckpt_dir=d, ckpt_every=5, log_every=0)
    # second run resumed (did not replay the first 10 steps)
    assert len(out2["losses"]) == 10
    assert out2["final_step"] == 20


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "xlstm-1.3b", "zamba2-2.7b"])
def test_serve_loop(arch):
    out = serve(arch, batch=2, prompt_len=16, max_new_tokens=4)
    assert out["new_tokens"] == 4
    assert len(out["sample"]) >= 4


@pytest.mark.parametrize("tokens", [1, 5])
def test_serve_token_accounting_is_exact(tokens):
    """The decode loop yields exactly max_new_tokens tokens — token 0 from
    the prefill logits, token i from the i-th decode step (the old loop got
    the count right only by counting the prefill token implicitly)."""
    out = serve("qwen2-1.5b", batch=2, prompt_len=8, max_new_tokens=tokens)
    assert out["new_tokens"] == tokens


def test_serve_seed_changes_prompts_not_shape():
    """PRNG is explicit: one seed key splits per use, so different seeds
    give different streams of the same shape."""
    a = serve("qwen2-1.5b", batch=2, prompt_len=8, max_new_tokens=3, seed=0)
    b = serve("qwen2-1.5b", batch=2, prompt_len=8, max_new_tokens=3, seed=1)
    assert a["new_tokens"] == b["new_tokens"] == 3
    assert a["sample"] != b["sample"]  # independent prompt draws


def test_onn_retrieval_service():
    solver, xi = build_solver("7x6", "hybrid")
    out = serve_requests(solver, xi, corruption=0.10, n_requests=64)
    assert out["accuracy"] >= 0.9, out  # paper: ~100 % at 10 % corruption
    assert out["mean_settle_cycles"] < 50


def test_maxcut_service():
    """Engine-served Ising machine: cuts beat the random baseline on every
    instance and requests carry the recurrent-vs-hybrid hardware quote."""
    solver = MaxCutSolver(sweeps=24, replicas=4, stagnation=6, backend="hybrid", parallel_factor=8)
    out = serve_cuts(solver, n=24, n_requests=8, seed=3)
    assert out["min_ratio_vs_half_edges"] > 1.0, out
    assert out["mean_sweeps_run"] <= 24
    assert out["estimate"]["fpga_tradeoff"] is not None
    assert out["estimate"]["fpga_tradeoff"]["hybrid[P=8]"] is not None
    assert out["engine"]["maxcut"]["backend"] == "hybrid"


def test_maxcut_service_deterministic_across_bucket_policy():
    """The serving-path determinism guarantee end to end: same instances +
    same seed ⇒ same cuts under exact and pow2 bucketing."""
    solver = MaxCutSolver(sweeps=12, replicas=2)
    a = serve_cuts(solver, n=20, n_requests=4, seed=5, n_policy="exact")
    b = serve_cuts(solver, n=20, n_requests=4, seed=5, n_policy="pow2")
    assert a["mean_cut"] == b["mean_cut"]
    assert a["mean_ratio_vs_half_edges"] == b["mean_ratio_vs_half_edges"]


def test_onn_retrieval_via_pallas_kernel():
    """The Pallas coupling kernel must reproduce the jnp path exactly."""
    solver_k, xi = build_solver("5x4", "hybrid", backend="pallas")
    solver_j, _ = build_solver("5x4", "hybrid", backend="parallel")
    out_k = serve_requests(solver_k, xi, corruption=0.10, n_requests=32)
    out_j = serve_requests(solver_j, xi, corruption=0.10, n_requests=32)
    assert out_k["accuracy"] == out_j["accuracy"], (out_k, out_j)
    assert out_k["mean_settle_cycles"] == out_j["mean_settle_cycles"]


def test_train_onn_hot_swap_flow(tmp_path):
    """train_onn end to end: Hebbian baseline served, QAT-DO-I trained and
    hot-installed mid-stream through a checkpoint round trip, accuracy
    improves, and the swap compiles nothing."""
    from repro.launch.train_onn import run_train_serve

    out = run_train_serve(
        dataset="7x6", corruption=0.15, probes=12, seed=0,
        ckpt_dir=str(tmp_path), max_sweeps=200,
    )
    assert out["train"]["converged"]
    assert out["accuracy_trained"] >= out["accuracy_hebbian"]
    assert out["hot_swaps"] == 1
    assert out["serving_retraces_after_swap"] == 0
    assert out["checkpoint"] is not None
    assert out["completed"] == 3 * out["probes"]  # warmup + two phases


# ---------------------------------------------------------------------------
# Process and compile-cache rules of the entry points
# ---------------------------------------------------------------------------

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_python(args, cwd, **env):
    full_env = {**os.environ, "PYTHONPATH": os.path.join(REPO_ROOT, "src"), **env}
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=full_env, capture_output=True,
        text=True, timeout=300,
    )


def test_chip_smoke_refuses_the_cpu():
    """No silent CPU fallback: without a TPU the smoke run exits non-zero
    and never prints its ok line."""
    out = _run_python([os.path.join(REPO_ROOT, "chip_smoke.py")], REPO_ROOT, JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_alone_fails(tmp_path):
    """Copied away from the checkout, the script cannot reach the program."""
    shutil.copy(os.path.join(REPO_ROOT, "chip_smoke.py"), tmp_path)
    out = _run_python(["chip_smoke.py"], str(tmp_path), JAX_PLATFORMS="cpu", PYTHONPATH="")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_compile_cache_keeps_a_preset_directory(monkeypatch, tmp_path):
    import jax

    from repro.launch import compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_one_checkout_path(monkeypatch):
    import jax

    from repro.launch import compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = compile_cache.enable_compile_cache()
        assert compile_cache.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == first
        assert os.path.dirname(first) == REPO_ROOT
        with open(os.path.join(REPO_ROOT, ".gitignore")) as f:
            assert os.path.basename(first) + "/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_importing_dryrun_leaves_xla_flags_alone():
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO_ROOT, "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c",
         "import os, repro.launch.dryrun; print(os.environ.get('XLA_FLAGS'))"],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().splitlines()[-1] == "None"
