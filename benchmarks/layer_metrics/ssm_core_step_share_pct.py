"""The share of the step that the mixers no other cell runs take: the
device self time a step under ``attn.core`` with the sub-scopes ``scan``,
``conv``, ``decay``, ``gate`` (a Mamba layer's core), ``diff`` (the
differential core of window, whole and cross layers), ``gmu`` and
``handed`` (what the memory layers hand on, and backward its gradient
summed over the readers) over the device's busy time a step, both from ``benchmarks/device_scopes.py``'s table
of the traced steps.  The number the cell's ``why`` quotes: the SwiGLU is a
published width and is not cut, so this says what is left beside it.
Nothing to read where the program has no such scopes."""

from benchmarks import device_scopes

SUB_SCOPES = ("scan", "conv", "decay", "gate", "diff", "gmu", "handed")


def read(observed):
    table = device_scopes.table_of(observed)
    if not table or not table["busy_ms"]:
        return None
    found = {sub for (kind, sub, _) in table["rows"] if kind == "attn.core"}
    if "scan" not in found:
        return None
    took_ms = sum(row[0] for (kind, sub, _), row in table["rows"].items()
                  if kind == "attn.core" and sub in SUB_SCOPES)
    return 100.0 * took_ms / table["busy_ms"]
