"""Tier-1 runs ``tests/`` alone, and the LFM2 family's benchmark tests live
with the benchmark (``benchmarks/tests/*_lfm2.py``): this file brings them
in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_lfm2 import *  # noqa: F401,F403
from benchmarks.tests.test_flops_lfm2 import *  # noqa: F401,F403
from benchmarks.tests.test_reference_lfm2 import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_lfm2 import *  # noqa: F401,F403

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """As ``tests/test_benchmark_ling3.py``: several families' compiled
    programs in one worker's process crashed XLA's CPU compile of the next
    (PR 48); let this file's go when it is done."""
    yield
    import jax

    jax.clear_caches()
