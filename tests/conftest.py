import os
import sys
import types

import pytest

# src layout import without install; tests run single-device (the 512-device
# override belongs ONLY to the dry-run entry point)
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# tests never use the persistent compilation cache — neither in this process
# nor in the entry points they start as subprocesses (repro.launch.compile_cache)
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute tests (subprocess re-runs under forced device "
        "counts); deselect with -m 'not slow'",
    )


_MAPS = "/proc/self/maps"
_MAPS_HIGH_WATER = 20_000


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """Release the compiled programs once a worker holds too many. XLA:CPU
    maps code pages for every executable the jit caches keep alive (a tree
    build compiles dozens); a worker that runs many tests reaches the
    kernel's 65,530-mapping limit, and its next compile crashes the process.
    Clearing at 20,000 leaves room for the largest single test."""
    yield
    if os.path.exists(_MAPS):
        with open(_MAPS) as f:
            n_maps = sum(1 for _ in f)
        if n_maps > _MAPS_HIGH_WATER:
            import jax

            jax.clear_caches()


# ---------------------------------------------------------------------------
# hypothesis guard: the property tests import `hypothesis` at module scope, so
# a missing install used to kill collection of six whole modules. When the
# package is absent, install a shim that (a) lets the modules import, and
# (b) turns every @given test into a clean pytest skip — the non-property
# tests in those modules still run. `pip install -r requirements-dev.txt`
# restores the real thing.
# ---------------------------------------------------------------------------
try:
    import hypothesis  # noqa: F401
except ImportError:  # pragma: no cover - exercised only without hypothesis
    import pytest

    def _given(*_args, **_kwargs):
        def deco(fn):
            def _skipped():
                pytest.skip("hypothesis not installed (see requirements-dev.txt)")

            _skipped.__name__ = fn.__name__
            _skipped.__doc__ = fn.__doc__
            return _skipped

        return deco

    def _settings(*_args, **_kwargs):
        def deco(fn):
            return fn

        return deco

    class _Strategies(types.ModuleType):
        def __getattr__(self, name):
            def _strategy(*_args, **_kwargs):
                return None

            _strategy.__name__ = name
            return _strategy

    _st = _Strategies("hypothesis.strategies")
    _hyp = types.ModuleType("hypothesis")
    _hyp.given = _given
    _hyp.settings = _settings
    _hyp.strategies = _st
    _hyp.assume = lambda *_a, **_k: True
    _hyp.note = lambda *_a, **_k: None
    _hyp.example = _settings
    sys.modules["hypothesis"] = _hyp
    sys.modules["hypothesis.strategies"] = _st
