"""Make ``bench`` and the program (``src/``) importable in these tests.

Run them on the CPU: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``.
A test that drives a whole run skips the harness's look for a chip with
the ``no_chip_check`` fixture.
"""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


@pytest.fixture
def no_chip_check(monkeypatch):
    """``harness.require_chips`` returns JAX's devices, whatever they are."""
    import jax
    from bench import harness
    monkeypatch.setattr(harness, "require_chips",
                        lambda chips: jax.devices()[:max(chips, 1)])
