"""``functools.lru_cache`` across the run's xdist workers.

The kernel-against-``jnp`` files compute a case's results once (the
kernels in the interpreter, the ``jax.numpy`` body, every gradient of
each) and assert a quantity a test.  ``--dist load`` hands out contiguous
runs of the collection order, so the cases of one key stand next to each
other; where a run still ends inside a key, the second worker reads what
the first computed from a file under ``conftest.RUN_DIR`` (or waits for
it) and computes nothing.  The directory is the session's own, made and
removed by its controller: what is read there, this run of this tree
computed."""

import fcntl
import functools
import os
import pickle

import conftest
import jax


def shared_memo(fn):
    """Memoise ``fn``, a function of strings and numbers that returns a
    tree of arrays (handed back as ``numpy``'s), for this process and,
    through the run's directory, for every worker of the run."""
    @functools.lru_cache(maxsize=None)
    def memo(*args, **kwargs):
        key = (fn.__module__, fn.__name__) + args + tuple(
            f"{name}={value}" for name, value in sorted(kwargs.items()))
        path = os.path.join(
            conftest.RUN_DIR, "memo", "-".join(map(str, key)))
        with open(path + ".lock", "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)    # one worker computes
            try:
                with open(path, "rb") as f:
                    return pickle.load(f)
            except FileNotFoundError:
                pass
            value = jax.device_get(fn(*args, **kwargs))
            with open(path + ".tmp", "wb") as f:
                pickle.dump(value, f)
            os.replace(path + ".tmp", path)
            return value
    return memo
