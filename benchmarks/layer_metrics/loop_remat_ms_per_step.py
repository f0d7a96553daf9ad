"""Device self time a step of everything a looped stack computes AGAIN in
its backward pass: every instruction whose pass is ``remat`` in the
program's scope table (``benchmarks/device_scopes.py``: under
``rematted_computation`` in its ``op_name``), whatever its kind: the 32
layer applications' forward passes (norms, projections, RoPE, the FA2
forward kernel, the SwiGLU), the final norm and the four heads' logits by
blocks of rows.  What the loop's memory costs in time: a policy that keeps
more reads lower here and higher in ``hbm_peak_gib``.  Nothing to read where
the program has no scopes or rematerialises nothing."""

from benchmarks import device_scopes


def read(observed):
    table = device_scopes.table_of(observed)
    if not table:
        return None
    rows = [row[0] for (_, _, which), row in table["rows"].items()
            if which == "remat"]
    return sum(rows) if rows else None
