"""The share of the step that the Mamba-2 mixers' cores take: the device
self time a step under ``attn.core`` with the sub-scopes ``ssd`` (the
chunked scan), ``conv``, ``decay`` and ``gate`` (the gate with its group
norm) over the device's busy time a step, both from
``benchmarks/device_scopes.py``'s table of the traced steps.  The number the
cell's ``why`` quotes: the mixers' projections are matmuls like any other,
this is what the new mechanism costs beside them.  Nothing to read where
the program has no ``ssd`` scope."""

from benchmarks import device_scopes

SUB_SCOPES = ("ssd", "conv", "decay", "gate")


def read(observed):
    table = device_scopes.table_of(observed)
    if not table or not table["busy_ms"]:
        return None
    found = {sub for (kind, sub, _) in table["rows"] if kind == "attn.core"}
    if "ssd" not in found:
        return None
    took_ms = sum(row[0] for (kind, sub, _), row in table["rows"].items()
                  if kind == "attn.core" and sub in SUB_SCOPES)
    return 100.0 * took_ms / table["busy_ms"]
