"""Tier-1 runs ``tests/`` alone, and the tests of the step ledger's readers
live with the benchmark (``benchmarks/tests/test_step_ledger.py``): this file
brings them in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_step_ledger import *  # noqa: F401,F403
