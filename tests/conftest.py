import sys
import types

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


# --------------------------------------------------------------------------
# `slow` marker: multi-epoch equivalence-grid cells (and other nightly-depth
# tests) are skipped by the tier-1 run (`pytest -x -q`); run them with
# `pytest --runslow` (or `-m slow` plus --runslow for only them).
# --------------------------------------------------------------------------
def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow (nightly depth)")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: nightly-depth test, skipped unless --runslow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="slow: nightly depth, use --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


# --------------------------------------------------------------------------
# Minimal deterministic `hypothesis` shim.
#
# The property tests use a small slice of the hypothesis API (given /
# settings / example / strategies.{integers,floats,lists}).  When the real package is
# unavailable we install a seeded stand-in that draws `max_examples` random
# examples per test, so the property tests still run (with fixed seeds)
# instead of failing at collection.  If hypothesis is installed it wins.
# --------------------------------------------------------------------------
try:                                                    # pragma: no cover
    import hypothesis  # noqa: F401
except ImportError:
    class _Strategy:
        def __init__(self, draw):
            self.draw = draw

    def _integers(lo, hi):
        return _Strategy(lambda r: int(r.integers(lo, hi + 1)))

    def _floats(lo, hi, **_kw):
        return _Strategy(lambda r: float(r.uniform(lo, hi)))

    def _booleans():
        return _Strategy(lambda r: bool(r.integers(0, 2)))

    def _sampled_from(seq):
        seq = list(seq)
        return _Strategy(lambda r: seq[int(r.integers(0, len(seq)))])

    def _lists(elem, min_size=0, max_size=10):
        def draw(r):
            n = int(r.integers(min_size, max_size + 1))
            return [elem.draw(r) for _ in range(n)]
        return _Strategy(draw)

    def _tuples(*elems):
        return _Strategy(lambda r: tuple(e.draw(r) for e in elems))

    def _example(**pinned):
        def deco(fn):
            fn._hyp_examples = [pinned] + getattr(fn, "_hyp_examples", [])
            return fn
        return deco

    def _settings(max_examples=20, deadline=None, **_kw):
        def deco(fn):
            fn._hyp_max_examples = max_examples
            return fn
        return deco

    def _given(**strategies):
        def deco(fn):
            n = getattr(fn, "_hyp_max_examples", 20)
            pinned = getattr(fn, "_hyp_examples", [])

            # no functools.wraps: pytest must see the (*args, **kwargs)
            # signature, not the original one (whose params would otherwise
            # be resolved as fixtures)
            def wrapper(*args, **kwargs):
                for drawn in pinned:
                    fn(*args, **kwargs, **drawn)
                r = np.random.default_rng(0)
                for _ in range(n):
                    drawn = {k: s.draw(r) for k, s in strategies.items()}
                    fn(*args, **kwargs, **drawn)
            wrapper.__name__ = fn.__name__
            wrapper.__doc__ = fn.__doc__
            wrapper.__module__ = fn.__module__
            return wrapper
        return deco

    _st = types.ModuleType("hypothesis.strategies")
    _st.integers = _integers
    _st.floats = _floats
    _st.booleans = _booleans
    _st.sampled_from = _sampled_from
    _st.lists = _lists
    _st.tuples = _tuples

    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.example = _example
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.__is_repro_stub__ = True

    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
