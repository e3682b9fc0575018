"""The benchmark's one traffic generator, driven by a traffic file's data.

``"loop": "closed"`` — waves: ``wave_requests`` requests go through
``Engine.submit``, then one ``Engine.drain()``; the results are fetched to
the host and the next wave starts.  Waves repeat until the window's time is
up; the wave under way finishes.  Completion of a request is its result on
the host.

Host spans (``jax.profiler.TraceAnnotation``, named ``bench.*``) mark what
the host was doing, so a traced run can attribute the device's idle gaps.
"""

from __future__ import annotations

import time

import jax

WORKLOAD = "cell"


def _span(name):
    return jax.profiler.TraceAnnotation(name)


class Outcome:
    """What a window produced: its results and host times."""

    def __init__(self):
        self.served = []  # (request key for the check, result fields as numpy)
        self.failed = 0
        self.attempted = 0
        self.t_start = 0.0
        self.t_last = 0.0
        self.host = {"submit_s": 0.0, "drain_s": 0.0}


def _fields(res):
    return {k: v for k, v in res._asdict().items() if v is not None}


def make_engine(traffic, key):
    from repro.engine import Engine

    return Engine(key)


def closed(engine, system, traffic, seconds, first, clock=time.perf_counter):
    """Waves of ``wave_requests`` (at least one); request numbers start at
    ``first``."""
    from repro.engine import Request

    out = Outcome()
    wave = traffic["wave_requests"]
    i = first
    out.t_start = clock()
    while True:
        futs = []
        for _ in range(wave):
            payload, key, ident = system.request(i)
            i += 1
            a = clock()
            with _span("bench.submit"):
                futs.append((ident, engine.submit(Request(WORKLOAD, payload, key=key))))
            out.host["submit_s"] += clock() - a
        a = clock()
        with _span("bench.drain"):
            engine.drain()
        out.host["drain_s"] += clock() - a
        with _span("bench.fetch"):
            for ident, f in futs:
                out.attempted += 1
                exc = f.exception()
                if exc is not None:
                    out.failed += 1
                    continue
                out.served.append((ident, jax.device_get(_fields(f.result()))))
        out.t_last = clock()
        if out.t_last - out.t_start >= seconds:
            return out


def drive(engine, system, traffic, seconds, first):
    with system.context():
        return closed(engine, system, traffic, seconds, first)
