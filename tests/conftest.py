import os
import sys

# NOTE: no XLA_FLAGS here — smoke tests and benches must see 1 device.
# Multi-device tests spawn subprocesses that set the flag themselves.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", False)


# --------------------------------------------------------------------------
# hypothesis (optional dependency)
# --------------------------------------------------------------------------

def require_hypothesis():
    """Skip the calling module unless hypothesis is installed, then return
    its ``(given, settings, strategies)`` triple. For modules that are
    hypothesis-only (tests/test_property.py, tests/test_spec_property.py):

        given, settings, st = require_hypothesis()
    """
    import pytest
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies
    return given, settings, strategies


def optional_hypothesis():
    """``(given, settings, strategies)`` or None — for modules whose
    hypothesis tests ride alongside env-independent ones
    (tests/test_radix_property.py): the module keeps collecting, only the
    decorated tests disappear when the wheel is absent."""
    try:
        from hypothesis import given, settings, strategies
    except ImportError:
        return None
    return given, settings, strategies
