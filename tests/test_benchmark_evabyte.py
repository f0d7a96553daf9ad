"""Tier-1 runs ``tests/`` alone, and the EvaByte family's benchmark tests
live with the benchmark (``benchmarks/tests/*_evabyte.py``): this file brings
them in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_evabyte import *  # noqa: F401,F403
from benchmarks.tests.test_flops_evabyte import *  # noqa: F401,F403
from benchmarks.tests.test_reference_evabyte import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_evabyte import *  # noqa: F401,F403
