"""Optional-hypothesis shim for the property-test modules.

``hypothesis`` is a dev-only dependency (requirements-dev.txt).  When it is
missing, the deterministic tests in a module must still collect and run, so
this module degrades gracefully: ``@given(...)`` turns the property test
into a skip, ``@settings(...)`` and ``@example(...)`` become no-ops, and
``st.<anything>(...)`` returns inert placeholders that are only ever passed
to the stubbed ``given``.

When hypothesis IS present, importing this module registers the repo's
settings profiles (all with the deadline off — JAX dispatch latency is too
jittery for per-example deadlines — and derandomized, so CI failures
reproduce from the seed alone):

  ci       the PR-gate default: few examples, fast
  nightly  the ``schedule:`` CI runs: an order of magnitude more examples
  dev      local iteration: randomized for exploration

``HYPOTHESIS_PROFILE`` selects one (ci.yml sets it per trigger).
"""

import os

try:
    from hypothesis import (  # noqa: F401
        HealthCheck,
        example,
        given,
        settings,
        strategies as st,
    )

    HAVE_HYPOTHESIS = True

    _COMMON = dict(
        deadline=None,
        suppress_health_check=[
            HealthCheck.too_slow,
            HealthCheck.data_too_large,
        ],
    )
    settings.register_profile(
        "ci", max_examples=25, derandomize=True, **_COMMON
    )
    settings.register_profile(
        "nightly", max_examples=300, derandomize=True, **_COMMON
    )
    settings.register_profile("dev", max_examples=50, **_COMMON)
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))
except ImportError:  # pragma: no cover - exercised only without hypothesis
    import pytest

    HAVE_HYPOTHESIS = False

    class _StrategyStub:
        """st.integers(...)/st.lists(...)/... -> inert placeholder."""

        def __getattr__(self, name):
            def _strategy(*args, **kwargs):
                return None

            return _strategy

    st = _StrategyStub()

    def settings(*args, **kwargs):
        def deco(fn):
            return fn

        return deco

    example = settings

    def given(*args, **kwargs):
        def deco(fn):
            return pytest.mark.skip(reason="hypothesis not installed")(fn)

        return deco
