"""Tier-1 runs ``tests/`` alone, and the SDAR family's benchmark tests live
with the benchmark (``benchmarks/tests/*_sdar.py``): this file brings them
in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_sdar import *  # noqa: F401,F403
from benchmarks.tests.test_flops_sdar import *  # noqa: F401,F403
from benchmarks.tests.test_reference_sdar import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_sdar import *  # noqa: F401,F403
