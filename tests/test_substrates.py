"""Substrate tests: optimizers, checkpointing, data pipeline, fault tolerance,
gradient compression."""

import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro import checkpoint as ckpt
from repro import optim
from repro.data.tokens import TokenStream
from repro.distributed import ft
from repro.models.params import ParamSpec
from repro.optim import compress


# ---------------------------------------------------------------------------
# Optimizers
# ---------------------------------------------------------------------------


def _quad_params():
    return {"w": jnp.array([1.0, -2.0, 3.0], jnp.float32), "b": jnp.array(0.5)}


@pytest.mark.parametrize("name", ["adamw", "adafactor"])
def test_optimizer_minimizes_quadratic(name):
    params = _quad_params()
    opt = optim.get_optimizer(name, optim.constant(0.1), weight_decay=0.0)
    state = opt.init(params)

    def loss(p):
        return jnp.sum(p["w"] ** 2) + p["b"] ** 2

    l0 = float(loss(params))
    for _ in range(200):
        grads = jax.grad(loss)(params)
        params, state, _ = opt.update(grads, state, params)
    assert float(loss(params)) < 1e-2 * l0


def test_adamw_state_specs_match_shapes():
    specs = {"w": ParamSpec((8, 4), ("embed", "mlp")), "b": ParamSpec((4,), (None,))}
    opt = optim.adamw(optim.constant(1e-3))
    st = opt.state_specs(specs)
    assert st["m"]["w"].shape == (8, 4) and st["v"]["b"].shape == (4,)
    assert st["m"]["w"].axes == ("embed", "mlp")


def test_adafactor_factored_specs():
    specs = {"w": ParamSpec((256, 512), ("embed", "mlp")), "b": ParamSpec((4,), (None,))}
    opt = optim.adafactor(optim.constant(1e-3))
    st = opt.state_specs(specs)
    assert st["stats"]["w"]["vr"].shape == (256,)
    assert st["stats"]["w"]["vc"].shape == (512,)
    assert "v" in st["stats"]["b"]  # too small to factor


def test_cosine_warmup_schedule():
    sched = optim.cosine_warmup(1.0, warmup=10, total=110, floor=0.1)
    assert float(sched(jnp.int32(0))) == 0.0
    assert abs(float(sched(jnp.int32(10))) - 1.0) < 1e-6
    assert abs(float(sched(jnp.int32(110))) - 0.1) < 1e-6
    assert float(sched(jnp.int32(60))) < 1.0


def test_clip_by_global_norm():
    tree = {"a": jnp.full((4,), 10.0)}
    clipped, norm = optim.clip_by_global_norm(tree, 1.0)
    assert abs(float(norm) - 20.0) < 1e-5
    assert abs(float(optim.global_norm(clipped)) - 1.0) < 1e-5


# ---------------------------------------------------------------------------
# Checkpointing
# ---------------------------------------------------------------------------


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 8)), "b": jnp.zeros((8,))},
        "step": jnp.int32(7),
    }


def test_checkpoint_roundtrip(tmp_path):
    d = str(tmp_path)
    tree = _tree()
    ckpt.save(d, 7, tree)
    assert ckpt.latest_step(d) == 7
    out = ckpt.restore(d, 7, jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_retention_and_latest(tmp_path):
    d = str(tmp_path)
    for s in (1, 2, 3, 4, 5):
        ckpt.save(d, s, _tree(), keep=2)
    assert ckpt.all_steps(d) == [4, 5]
    assert ckpt.latest_step(d) == 5


def test_checkpoint_atomicity(tmp_path):
    """A stale .tmp dir (crash mid-write) must not be seen as a checkpoint."""
    d = str(tmp_path)
    ckpt.save(d, 1, _tree())
    os.makedirs(os.path.join(d, "step_2.tmp"))  # simulated crash
    assert ckpt.latest_step(d) == 1


def test_checkpoint_elastic_restore_new_mesh(tmp_path):
    """Restore onto a different sharding (elastic re-mesh after node loss)."""
    d = str(tmp_path)
    tree = _tree()
    ckpt.save(d, 3, tree)
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    target = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
    shardings = jax.tree.map(lambda x: sh if x.shape else None, target)
    # degenerate 1-device mesh here; the API path is identical at scale
    shardings["step"] = None
    out = ckpt.restore(d, 3, target, shardings)
    np.testing.assert_allclose(
        np.asarray(out["params"]["w"]), np.asarray(tree["params"]["w"])
    )


def test_async_checkpointer(tmp_path):
    d = str(tmp_path)
    saver = ckpt.AsyncCheckpointer(d, keep=2)
    for s in (10, 20):
        saver.save(s, _tree(s))
    saver.wait()
    assert ckpt.all_steps(d) == [10, 20]
    meta = ckpt.load_meta(d, 20)
    assert meta["step"] == 20


def test_propose_mesh_elastic():
    assert ft.propose_mesh(256) == (16, 16)
    assert ft.propose_mesh(240, prefer_model=16) == (15, 16)  # still divisible
    assert ft.propose_mesh(250, prefer_model=16) == (125, 2)  # degrade model TP
    assert ft.propose_mesh(7) == (7, 1)


# ---------------------------------------------------------------------------
# Data pipeline
# ---------------------------------------------------------------------------


def test_tokenstream_determinism_and_cursor():
    a = TokenStream(1000, 8, 32, seed=3)
    b1 = [a.next() for _ in range(3)]
    state = a.state()
    b2 = [a.next() for _ in range(2)]
    a.close()

    b = TokenStream(1000, 8, 32, seed=3)
    c1 = [b.next() for _ in range(3)]
    b.restore(state)
    c2 = [b.next() for _ in range(2)]
    b.close()
    for x, y in zip(b1 + b2, c1 + c2):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])


def test_tokenstream_host_sharding():
    full = TokenStream(100, 8, 16, seed=1, host_id=0, n_hosts=1)
    h0 = TokenStream(100, 8, 16, seed=1, host_id=0, n_hosts=2)
    h1 = TokenStream(100, 8, 16, seed=1, host_id=1, n_hosts=2)
    x0, x1 = h0.next(), h1.next()
    assert x0["tokens"].shape == (4, 16) and x1["tokens"].shape == (4, 16)
    assert not np.array_equal(x0["tokens"], x1["tokens"])
    for s in (full, h0, h1):
        s.close()


def test_tokenstream_labels_shifted():
    s = TokenStream(50, 2, 16, seed=0)
    b = s.next()
    s.close()
    assert b["tokens"].shape == b["labels"].shape == (2, 16)
    # autoregressive alignment: token stream is contiguous
    # (labels are the next-token view of the same underlying sequence)
    assert b["tokens"][0, 1:].tolist() == b["labels"][0, :-1].tolist()


# ---------------------------------------------------------------------------
# Fault tolerance
# ---------------------------------------------------------------------------


def test_step_monitor_flags_straggler():
    events = []
    mon = ft.StepMonitor(z_threshold=3.0, warmup=3, on_straggler=events.append)
    for i in range(20):
        mon.observe(i, 0.1)  # steady steps
    assert not events
    mon.observe(99, 5.0)  # 50× step time — a straggler
    assert len(events) == 1 and events[0].step == 99
    # outlier must not poison the running mean
    assert mon.mean < 0.2


def test_preemption_guard_sets_flag():
    with ft.PreemptionGuard(signals=(signal.SIGUSR1,)) as g:
        assert not g.preempted
        os.kill(os.getpid(), signal.SIGUSR1)
        time.sleep(0.05)
        assert g.preempted


def test_heartbeat_staleness(tmp_path):
    p = str(tmp_path / "hb")
    hb = ft.Heartbeat(p, interval_s=0.0)
    hb.beat(1)
    assert not ft.Heartbeat.is_stale(p, max_age_s=10.0)
    assert ft.Heartbeat.is_stale(p + "missing", max_age_s=10.0)


# ---------------------------------------------------------------------------
# Gradient compression (error feedback)
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_bounds():
    x = jnp.array([-3.0, 0.0, 1.5, 3.0])
    q, scale = compress.quantize(x)
    err = jnp.max(jnp.abs(compress.dequantize(q, scale) - x))
    assert float(err) <= float(scale) / 2 + 1e-7


def test_error_feedback_unbiased_over_steps():
    """Accumulated EF-compressed updates converge to accumulated true grads."""
    key = jax.random.PRNGKey(0)
    g_true = jax.random.normal(key, (64,)) * 0.01
    err = jnp.zeros((64,))
    total = jnp.zeros((64,))
    for i in range(50):
        q, scale, err = compress.ef_compress(g_true, err)
        total = total + compress.dequantize(q, scale)
    # mean reconstructed gradient ≈ true gradient (error stays bounded)
    np.testing.assert_allclose(np.asarray(total / 50), np.asarray(g_true), atol=1e-4)


def test_compressed_psum_mean_single_device():
    mesh = jax.make_mesh((1,), ("data",), (AxisType.Auto,))
    x = jnp.arange(8, dtype=jnp.float32) / 10
    e = jnp.zeros((8,))
    from jax.sharding import PartitionSpec as P
    import functools

    fn = jax.shard_map(
        functools.partial(compress.compressed_psum_mean, axis_name="data"),
        mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
    )
    mean, new_e = fn(x, e)
    np.testing.assert_allclose(np.asarray(mean + new_e), np.asarray(x), atol=1e-6)
