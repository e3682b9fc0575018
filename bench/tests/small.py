"""Small sizes at which a cell runs on the CPU in seconds (tests only)."""

import run

SMALL = {
    "onn506.batch": (
        {"n": 64, "couplings": {"rule": "qat_doi", "patterns": 6, "qat_bits": 5,
                                "threshold": 1.0, "max_sweeps": 100}},
        {"pool": 64, "wave_requests": 8}),
    "maxcut_g1.batch": (
        {"n": 40, "sweeps": 4, "replicas": 2},
        {"pool": 8, "pool_chunk": 4, "wave_requests": 4, "keys": 64, "reference_block": 8}),
}


def run_small(cell, seed, *, seconds=1.0, control=False, config=None):
    """One run of ``cell`` at its small size (with ``config`` on top) on
    whatever JAX finds."""
    cfg, traffic = SMALL[cell]
    return run.run_cell(cell, seed, seconds, False, control=control, require_tpu=False,
                        config_overrides={**cfg, **(config or {})}, traffic_overrides=traffic)
