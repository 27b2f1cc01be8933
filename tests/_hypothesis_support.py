"""Optional-hypothesis shim.

Tier-1 must collect and pass on a bare interpreter (no ``hypothesis``):
property tests import ``given``/``settings``/``example``/``st`` from
here.  When
hypothesis is available this is a transparent re-export; when it is not,
``@given`` replaces the test with a zero-argument stub marked skip (the
strategy-valued parameters would otherwise be collected as fixtures).
"""
import pytest

try:
    from hypothesis import (example, given, settings,  # noqa: F401
                            strategies as st)
    HAS_HYPOTHESIS = True
except ImportError:
    HAS_HYPOTHESIS = False

    class _AnyStrategy:
        """Stands in for ``hypothesis.strategies``: every attribute is a
        callable returning None (the value is never used -- the test body
        is replaced by a skip stub)."""

        def __getattr__(self, _name):
            def _strategy(*_args, **_kwargs):
                return None
            return _strategy

    st = _AnyStrategy()

    def settings(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    example = settings

    def given(*_args, **_kwargs):
        def deco(fn):
            @pytest.mark.skip(reason="hypothesis not installed")
            def _skipped():
                pass
            _skipped.__name__ = fn.__name__
            _skipped.__doc__ = fn.__doc__
            return _skipped
        return deco
