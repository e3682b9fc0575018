"""No-op stand-ins for ``hypothesis`` so suites degrade to skips without it.

``hypothesis`` is an optional dev dependency (declared in pyproject.toml).
Mixed test modules guard their import with::

    try:
        from hypothesis import example, given, settings
        from hypothesis import strategies as st
    except ModuleNotFoundError:
        from hypothesis_fallback import example, given, settings, st

so their non-property tests still collect and run; each ``@given`` test is
marked skipped instead of failing collection.  (Modules that are *entirely*
property-based use ``pytest.importorskip("hypothesis")`` instead.)
"""

import pytest


def given(*_args, **_kwargs):
    def deco(fn):
        return pytest.mark.skip(reason="hypothesis not installed")(fn)

    return deco


def example(*_args, **_kwargs):
    def deco(fn):
        return fn

    return deco


class settings:  # noqa: N801 — mirrors hypothesis.settings
    def __init__(self, *_args, **_kwargs):
        pass

    def __call__(self, fn):
        return fn

    @staticmethod
    def register_profile(*_args, **_kwargs):
        pass

    @staticmethod
    def load_profile(*_args, **_kwargs):
        pass


class _AnyStrategy:
    """Accepts any strategies.<name>(...) call; values are never drawn."""

    def __getattr__(self, _name):
        return lambda *args, **kwargs: None


st = _AnyStrategy()
hnp = _AnyStrategy()  # stands in for hypothesis.extra.numpy
