"""Shared hypothesis set-up for the property-test modules."""
from __future__ import annotations

import hypothesis
import hypothesis.strategies as st  # noqa: F401  (re-exported)

given = hypothesis.given


def load_ci_profile(*, max_examples: int, suppress_too_slow: bool = False):
    """Register and load the deterministic CI profile."""
    kw = dict(deadline=None, max_examples=max_examples)
    if suppress_too_slow:
        kw["suppress_health_check"] = [hypothesis.HealthCheck.too_slow]
    hypothesis.settings.register_profile("ci", **kw)
    hypothesis.settings.load_profile("ci")
