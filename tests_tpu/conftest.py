"""Tier-3 live-TPU tests (SURVEY.md §4: "(3) opt-in real TPU jobs").

These run against the REAL TPU backend — they are deliberately outside
``tests/`` (whose conftest forces an 8-virtual-device CPU mesh).  Run on
a machine with a chip::

    python -m pytest tests_tpu/ -q

The ``tpu_backend`` fixture is the one gate: a test that needs the chip
takes it, and skips when the default backend is not a TPU.
"""

import pytest


@pytest.fixture(scope="session")
def tpu_backend():
    import jax

    if jax.default_backend() != "tpu":
        pytest.skip(f"default backend is {jax.default_backend()!r}, not tpu")
    return jax
