"""Shared fixtures.  NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see the real single CPU device; only launch/dryrun.py (and
the subprocess-based distributed tests) force a placeholder device count.
"""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))  # benchmarks/

try:  # property-test effort profiles; the nightly CI job selects "nightly"
    from hypothesis import HealthCheck, settings

    settings.register_profile(
        "default", max_examples=25, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.register_profile(
        "nightly", max_examples=200, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "default"))
except ImportError:  # tier-1 runs fixed-example fallbacks instead
    pass


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


def _copy_bytes(hlo: str, shapes) -> int:
    """Bytes that the ``copy`` ops of the compiled program ``hlo`` write
    into buffers of any of ``shapes`` (unit dims aside)."""
    import math
    import re

    def squeeze(shape):
        return tuple(d for d in shape if d != 1)

    wanted = {squeeze(s) for s in shapes}
    item = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4}
    total = 0
    for dt, dims in re.findall(r"=\s*(\w+)\[([\d,]*)\]\{[^ ]*\s+copy\(", hlo):
        shape = tuple(int(d) for d in dims.split(",") if d)
        if squeeze(shape) in wanted:
            total += math.prod(shape) * item[dt]
    return total


@pytest.fixture(scope="session")
def copy_bytes():
    """``(hlo text, shapes) -> bytes`` copied into buffers of those shapes."""
    return _copy_bytes
