"""Tier-1 runs ``tests/`` alone, and the Ling-3.0 family's benchmark tests
live with the benchmark (``benchmarks/tests/*_ling3.py``): this file brings
them in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_ling3 import *  # noqa: F401,F403
from benchmarks.tests.test_flops_ling3 import *  # noqa: F401,F403
from benchmarks.tests.test_reference_ling3 import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_ling3 import *  # noqa: F401,F403

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """This file's cases leave their compiled programs in the worker's
    process; with the sparse-attention family's file before it in the same
    worker, the next family's first compile then died in XLA's CPU backend
    (a segmentation fault in ``backend_compile_and_load``, in both of two
    whole runs and in one process of the three files; not with either file
    alone, PR 48): let them go when the file is done."""
    yield
    import jax

    jax.clear_caches()
