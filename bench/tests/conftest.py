"""CPU tests of the benchmark's own code (run by hand, outside tier-1):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Each test process keeps JAX's compilation cache in a temporary directory,
never in the checkout.
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path_factory, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path_factory.getbasetemp() / "jax_cache"))
