"""Tier-1 runs ``tests/`` alone, and the sparse-attention family's benchmark
tests live with the benchmark (``benchmarks/tests/*_keyevl.py``): this file
brings them in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_keyevl import *  # noqa: F401,F403
from benchmarks.tests.test_flops_keyevl import *  # noqa: F401,F403
from benchmarks.tests.test_reference_keyevl import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_keyevl import *  # noqa: F401,F403
