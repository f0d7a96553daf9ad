"""Tier-1 runs ``tests/`` alone, and the Solar-Open2 family's benchmark
tests live with the benchmark (``benchmarks/tests/*_solaropen2.py``): this
file brings them in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_solaropen2 import *  # noqa: F401,F403
from benchmarks.tests.test_flops_solaropen2 import *  # noqa: F401,F403
from benchmarks.tests.test_reference_solaropen2 import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_solaropen2 import *  # noqa: F401,F403
