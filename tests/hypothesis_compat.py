"""Make ``hypothesis`` optional for the test suite.

The real library is used when installed (see requirements-dev.txt).  When
it is missing — the tier-1 CI image ships only jax + pytest — property
tests fall back to a small deterministic sweep over each strategy's
boundary/representative values instead of failing at collection time.
The fallback intentionally mirrors only the four strategies this suite
uses (integers, floats, booleans, sampled_from), and runs every
``@example`` (placed below ``@given``) before the sweep.
"""
from __future__ import annotations

import itertools

try:
    from hypothesis import example, given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                       # pragma: no cover
    HAVE_HYPOTHESIS = False

    class _Strategy:
        def __init__(self, samples):
            self.samples = list(samples)

    class st:  # noqa: N801  (mimics hypothesis.strategies module)
        @staticmethod
        def integers(min_value, max_value):
            mid = (min_value + max_value) // 2
            return _Strategy(dict.fromkeys([min_value, mid, max_value]))

        @staticmethod
        def floats(min_value, max_value):
            mid = 0.5 * (min_value + max_value)
            return _Strategy(dict.fromkeys([min_value, mid, max_value]))

        @staticmethod
        def booleans():
            return _Strategy([False, True])

        @staticmethod
        def sampled_from(elements):
            return _Strategy(elements)

    def settings(**_kwargs):
        return lambda f: f

    def example(*args):
        def deco(f):
            f._examples = [args] + getattr(f, "_examples", [])
            return f
        return deco

    def given(*strategies):
        def deco(f):
            def wrapper():
                for args in getattr(f, "_examples", []):
                    f(*args)
                pools = [s.samples for s in strategies]
                for combo in itertools.islice(
                        itertools.product(*pools), 32):
                    f(*combo)
            wrapper.__name__ = f.__name__
            wrapper.__doc__ = f.__doc__
            return wrapper
        return deco


__all__ = ["HAVE_HYPOTHESIS", "example", "given", "settings", "st"]
