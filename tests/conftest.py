import os
import sys
import types

import pytest

# Tests see the real device set (1 CPU device); multi-device tests force
# more in a subprocess (repro.launch.forcedevices).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# ---------------------------------------------------------------------------
# hypothesis shim: property tests skip cleanly when the package is absent.
#
# `hypothesis` is an optional test dependency (declared in pyproject's
# [test] extra).  When it is not installed we register a minimal stub so
# test modules still *import* (example-based tests in the same files keep
# running) while every @given test reports SKIPPED instead of erroring at
# collection.
# ---------------------------------------------------------------------------

try:
    import hypothesis  # noqa: F401
except ImportError:
    class _AnyStrategy:
        """Stands in for strategy objects and strategy factories."""

        def __call__(self, *args, **kwargs):
            return self

        def __getattr__(self, name):
            return self

    _any = _AnyStrategy()

    def _given(*_args, **_kwargs):
        def deco(fn):
            # No functools.wraps: pytest must see a zero-arg signature,
            # not the strategy-driven parameters of the wrapped test.
            def skipper():
                pytest.skip("hypothesis not installed")
            skipper.__name__ = fn.__name__
            skipper.__doc__ = fn.__doc__
            skipper.__module__ = fn.__module__
            return skipper
        return deco

    def _settings(*_args, **_kwargs):
        def deco(fn):
            return fn
        return deco

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _st = types.ModuleType("hypothesis.strategies")
    _st.__getattr__ = lambda name: _any
    _hyp.strategies = _st
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
