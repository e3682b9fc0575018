#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell (``workloads`` in
``BENCHMARK.json``) names a configuration and a traffic mix; the harness
finds everything by name under ``bench/``:

* ``configs/<config>.json``: the deployment's sizes; its ``system`` names
  ``systems/<system>.py``, which builds the system under test from the seed
  and holds the plain reference it is checked against (``references/``);
* ``traffic/<traffic>.json``: parameters that ``load.py``, the one traffic
  generator, reads;
* ``end_to_end/<metric>.py`` and ``metrics/<metric>.py``: one reader per
  metric; ``kernels/<kernel>.py``: a kernel's operations and bytes per
  launch; ``peaks.json``: the chips' peaks; ``xplane.py``: the reduction of a
  profiler trace.

A run sets up (couplings, data and request pools from ``--seed``, the
engine, warm-up of every shape the traffic uses), measures for
``--seconds`` (with ``--trace 1`` under the profiler, and the per-layer
metrics instead of the end-to-end ones), then frees the program's state and
compares every result with the reference.  The last line of standard output
is one JSON object; the numbers compared, each beside its limit, are the
last lines of standard error and the result's last key.  Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.

``--control 1`` puts the reference at the next lower precision in the
program's place for the comparison; its result has to read not correct.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

#: Requests of the warm-up are numbered from here, apart from the window's.
WARMUP_FIRST = 1 << 30
#: Warm-up rounds at most, each one wave.
WARMUP_ROUNDS = 4


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(kind, name):
    return load_module(os.path.join(BENCH, kind, name + ".py"), f"bench_{kind}_{name}")


def resolve(workload):
    """(spec, cell, config, traffic) dicts of a workload of ``BENCHMARK.json``."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    traffic = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return spec, cell, config, traffic


class CompileCounter:
    """Counts JAX traces and backend compiles (from ``jax.monitoring``)."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
    }

    def __init__(self, jax):
        self.counts = {"traces": 0, "compiles": 0}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        key = self.EVENTS.get(event)
        if key:
            self.counts[key] += 1

    def snapshot(self):
        return dict(self.counts)

    def since(self, snap):
        return {k: self.counts[k] - snap[k] for k in self.counts}


class Context:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def _traced(self):
        return self.trace is not None and self.trace.devices

    def devices_used(self):
        return self.trace.devices[: self.chips]

    def idle_share(self):
        if not self._traced():
            return None
        lo, hi = self.lo, self.hi
        busy = [self.tr.busy_ns(d, lo, hi) for d in self.devices_used()]
        return 100.0 * (1.0 - (sum(busy) / len(busy)) / (hi - lo))

    def busy_s(self):
        if not self._traced():
            return 0.0
        busy = [self.tr.busy_ns(d, self.lo, self.hi) for d in self.devices_used()]
        return sum(busy) / len(busy) / 1e9

    def roofline(self, name):
        """Least time of the kernel's launches (ops at the int8 peak or bytes
        at HBM bandwidth, whichever is longer) over their device time, %."""
        if not self._traced():
            return None
        mod = reader("kernels", name)
        least = spent = 0.0
        bounds = {"compute": 0.0, "memory": 0.0}
        for dev in self.devices_used():
            for dur, res, args in self.tr.kernel(dev, mod.MATCH, self.lo, self.hi):
                ops, nbytes = mod.cost(res, args, self.cfg)
                t_ops = ops / self.peak["int8_ops_per_s"]
                t_mem = nbytes / self.peak["hbm_bytes_per_s"]
                least += max(t_ops, t_mem)
                bounds["compute" if t_ops >= t_mem else "memory"] += max(t_ops, t_mem)
                spent += dur / 1e9
        if spent <= 0:
            return None
        self.notes[f"{name}_roofline_bound"] = max(bounds, key=bounds.get)
        self.notes[f"{name}_kernel_s"] = spent / len(self.devices_used())
        return 100.0 * least / spent


def check_devices(jax, chips):
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(
            f"needs a TPU; JAX found {devices[0].platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    if len(devices) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def enable_cache(jax):
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR`` if
    set (JAX reads it itself), else ``<checkout>/.jax_cache``; every
    program is kept, however quick its compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def info(tag, **fields):
    print(f"bench-{tag} " + json.dumps(fields, default=float), flush=True)


def run_cell(workload, seed, seconds, trace, *, control=False, require_tpu=True,
             config_overrides=None, traffic_overrides=None):
    """One run of a cell; returns the result dict (the printed last line)."""
    spec, cell, cfg, traffic = resolve(workload)
    cfg.update(config_overrides or {})
    traffic.update(traffic_overrides or {})
    chips = cell["chips"]

    import jax

    import load
    import xplane as tr

    enable_cache(jax)
    if require_tpu:
        devices = check_devices(jax, chips)
    else:
        devices = jax.devices()
    kind = devices[0].device_kind
    peaks = load_json(BENCH, "peaks.json")["devices"]
    if require_tpu and kind not in peaks:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    peak = peaks.get(kind, next(iter(peaks.values())))
    counter = CompileCounter(jax)

    rng = np.random.default_rng(seed)
    system = load_module(os.path.join(BENCH, "systems", cfg["system"] + ".py"),
                         "bench_system").System(cfg, traffic, rng, chips)
    engine = load.make_engine(traffic, jax.random.PRNGKey(int(rng.integers(0, 2**32))))
    adapter = engine.install(load.WORKLOAD, system.engine_solver())

    # Warm-up: the cell's own traffic until a round traces nothing new.
    warm = []
    for r in range(WARMUP_ROUNDS):
        snap = counter.snapshot()
        load.drive(engine, system, traffic, 0.0, WARMUP_FIRST + r * 100000)
        warm.append(counter.since(snap))
        if warm[-1]["traces"] == 0 and warm[-1]["compiles"] == 0:
            break

    window = min(seconds, traffic.get("trace_seconds", seconds)) if trace else seconds
    trace_dir = os.path.join(ROOT, ".bench_trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    snap = counter.snapshot()
    t_window = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        outcome = load.drive(engine, system, traffic, window, 0)
    in_window = counter.since(snap)
    if trace:
        jax.profiler.stop_trace()
    setup_s = t_window - T_PROCESS

    mem = [d.memory_stats() or {} for d in devices[:chips]]
    peak_bytes = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    stats = engine.stats()
    probe = system.request(0)[0]
    # The paper's FPGA time for one request (hardware model), for the record.
    simulated = adapter.fpga_seconds(adapter.bucket(adapter.signature(probe), engine.n_policy))
    engine = adapter = None
    system.free()
    gc.collect()

    t_check = time.perf_counter()
    bad, cycles, record = system.check(outcome.served, control=control)
    check_s = time.perf_counter() - t_check
    useful = system.useful_ops(outcome.served, cycles) if not control else None
    missing = outcome.failed + (outcome.attempted - outcome.failed - len(outcome.served))

    parsed = None
    notes = {}
    if trace:
        parsed = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = Context(trace=parsed, tr=tr, outcome=outcome, cfg=cfg, traffic=traffic, chips=chips,
                  peak=peak, useful_ops=useful, setup_s=setup_s, window_s=0.0,
                  lo=0.0, hi=0.0, notes=notes)
    if parsed is not None:
        ctx.lo, ctx.hi = parsed.window()
        ctx.window_s = (ctx.hi - ctx.lo) / 1e9
    else:
        ctx.window_s = outcome.t_last - outcome.t_start

    metrics = {}
    if trace:
        chosen = [m for m in spec["per_layer"] if workload in m.get("workloads", [workload])]
        for m in chosen:
            v = reader("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        chosen = [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]
        for m in chosen:
            v = reader("end_to_end", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(peak_bytes)}
    result = {
        "correct": bool(bad == 0 and missing == 0 and outcome.attempted > 0),
        "attempted": outcome.attempted,
        "failed": missing,
        "metrics": metrics,
        "device": device,
    }
    if parsed is not None:
        device["busy_s"] = ctx.busy_s()
        device["window_s"] = ctx.window_s
        used = ctx.devices_used()
        ops, gaps = {}, {}
        for d in used:
            for name, ns in tr.top_ops(d, ctx.lo, ctx.hi):
                ops[name] = ops.get(name, 0.0) + ns / 1e9 / len(used)
            for name, ns in tr.idle_gaps(d, parsed.spans, ctx.lo, ctx.hi).items():
                gaps[name] = gaps.get(name, 0.0) + ns / 1e9 / len(used)
        result["breakdown"] = {
            "device_ops": sorted(([k, v] for k, v in ops.items()), key=lambda kv: -kv[1])[:10],
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:10],
        }
    result["checks"] = {
        "mismatched_requests": {"value": bad, "limit": 0},
        "missing_requests": {"value": missing, "limit": 0},
    }

    info("setup", setup_s=setup_s, **system.record,
         warmup_rounds=warm, compiles_in_window=in_window)
    info("window", seconds=ctx.window_s, attempted=outcome.attempted,
         served=len(outcome.served), check_s=check_s, control=control, **record, **notes)
    info("engine", **{k: stats[k] for k in ("slabs", "pad_fraction", "lane_occupancy")
                      if k in stats}, simulated_fpga_seconds_per_request=simulated)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                          control=bool(args.control))
    except NoAccelerator as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
