"""Test configuration: force an 8-virtual-device CPU JAX backend.

Mirrors the reference's tier-1/tier-2 test strategy (SURVEY.md §4): unit
tests never need real TPU hardware; multi-chip sharding is exercised on a
virtual CPU mesh via --xla_force_host_platform_device_count.

``JAX_PLATFORMS=cpu`` (what the driver's command sets) selects the CPU;
the ``jax.config.update`` below holds a bare ``pytest tests/`` to it too.

One persistent compile cache serves the whole run: most of a model-level
case is XLA compiling a program that another xdist worker, or an earlier
case, has compiled already.  The key holds the program, jaxlib and the
flags, so a hit cannot be another program; the directory is filled and
read by one host within one run, which is why the CPU entries' baked-in
host features (the reason the program keeps its own cache off on the CPU,
``trainer/bootstrap.py::_setup_compile_cache``) do not matter here.  It
lives in the session's own directory (``RUN_DIR``), which the controller
makes under the temporary directory when the session starts and removes
when it ends: every run starts cold, nothing grows from run to run, and
two sessions on one machine (the driver's two checkouts, a builder's
second ``pytest``) never see each other's files.
"""

import contextlib
import glob
import os
import shutil
import tempfile
import time

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("DLROVER_TPU_SOCKET_DIR", "/tmp/dlrover_tpu_test/sockets")
os.environ["DLROVER_TPU_PLATFORM"] = "cpu"
# Tier-1 compiles for a CPU to check what a program computes, never to run
# it fast: the CPU backend is asked for correct code, not for optimised code
# (XLA's backend level 0, LLVM's expensive passes off).  In the environment,
# so that every process the suite starts inherits it (the rehearsals,
# ``tpurun``'s workers, the drills), and set, not defaulted: a constant of
# the suite.  ``test_chip_compile.py`` reads what the TPU compiler makes of
# a program and takes it out again for its module (its ``topo`` fixture).
os.environ["JAX_DISABLE_MOST_OPTIMIZATIONS"] = "1"

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

#: where the sessions' directories stand, one ``run-*`` a session
RUNS_ROOT = os.path.join(tempfile.gettempdir(), "dlrover_tpu_test")
#: what the workers of THIS session share (the compile cache,
#: ``shared_memo``'s results); set in ``pytest_configure``
RUN_DIR = None


def pytest_configure(config):
    global RUN_DIR
    workerinput = getattr(config, "workerinput", None)
    if workerinput is not None:
        RUN_DIR = workerinput["run_dir"]
    else:
        # the controller (or a run without xdist), before any worker
        # starts; what a killed session left behind goes after a day
        os.makedirs(RUNS_ROOT, exist_ok=True)
        for left in glob.glob(os.path.join(RUNS_ROOT, "run-*")):
            with contextlib.suppress(OSError):  # another session's sweep
                if os.stat(left).st_mtime < time.time() - 86400:
                    shutil.rmtree(left, ignore_errors=True)
        RUN_DIR = tempfile.mkdtemp(prefix="run-", dir=RUNS_ROOT)
        os.mkdir(os.path.join(RUN_DIR, "memo"))
    # before anything compiles: JAX decides once a process whether the
    # cache is in use
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(RUN_DIR, "compile_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    # xdist, on the controller: each worker learns the directory
    node.workerinput["run_dir"] = RUN_DIR


def pytest_unconfigure(config):
    if RUN_DIR and not hasattr(config, "workerinput"):
        shutil.rmtree(RUN_DIR, ignore_errors=True)


def pytest_collection_modifyitems(items):
    """``test_device_events.py`` first.  Its cases hold a profiler session
    to the CPU's device events, and a process that has described a TPU for
    ``test_chip_compile.py`` captures none afterwards (0 events, PR 48: a
    worker that had run a chunk of that file was later handed this one,
    once the suite grew by a hundred cases).  At the head of the list they
    are in the first worker's first chunk, before any worker has compiled
    for a described chip; every worker collects the same order."""
    items.sort(key=lambda item: not item.nodeid.startswith(
        "tests/test_device_events.py"))
