"""Where the entry points keep JAX's persistent compilation cache.

A cold process compiles every executable it runs; with the persistent cache
a second process on the same code and device reads them back instead.  The
cache key includes its directory, so the directory must not move between
runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads
that variable itself, and nothing here overrides it) or one fixed directory
inside the checkout, ``<repo>/.jax_cache``, listed in ``.gitignore``.

Call :func:`enable_compile_cache` once from an entry point's ``main()``;
importing this module changes nothing.
"""

from __future__ import annotations

import os

#: The in-checkout cache directory used when the environment names none.
CHECKOUT_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is left to JAX, which reads it
    itself; otherwise the cache goes to :data:`CHECKOUT_CACHE_DIR`.
    """
    preset = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if preset:
        return preset
    import jax

    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
