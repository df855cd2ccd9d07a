import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import pytest

jax.config.update("jax_platforms", "cpu")

try:
    import hypothesis
    import hypothesis.strategies as _hst
except ModuleNotFoundError:  # pragma: no cover - exercised in minimal envs
    hypothesis = None
    _hst = None


def property_test(argnames, cases, strategies, max_examples=15):
    """Property-test decorator that degrades gracefully without hypothesis.

    With ``hypothesis`` installed (requirements-dev.txt) the test runs under
    ``@given(**strategies(st))``; without it, it runs as a plain parametrize
    over the deterministic ``cases`` so the suite still collects and covers
    the path.

    argnames:   "a,b,c" — pytest parametrize signature (fallback mode).
    cases:      deterministic fallback tuples matching ``argnames``.
    strategies: callable ``st_module -> dict`` of hypothesis strategies
                (lazy so the module is only touched when present).
    """
    def deco(f):
        if hypothesis is None:
            return pytest.mark.parametrize(argnames, cases)(f)
        return hypothesis.settings(max_examples=max_examples, deadline=None)(
            hypothesis.given(**strategies(_hst))(f))
    return deco
