"""Device self time a step of the gated delta-rule layers' core (Kimi Delta
Attention), all passes, on the first chip: the program's scopes of kind
``attn.core`` with the sub-scopes a delta-rule layer has and no other layer
does (``conv``: the short convolutions; ``decay``: the log decay, beta and
the norms of q and k; ``chunk``: the work inside chunks; ``state``: the scan
between chunks; ``gate``: the per-head norm and the output gate), from
``benchmarks/device_scopes.py``'s table.  By scope, not by shape: whatever
implements the layer is read the same.  Nothing to read where the program
has no such scope (an older commit, another family)."""

from benchmarks import device_scopes

KIND = "attn.core"
SUB_SCOPES = ("conv", "decay", "chunk", "state", "gate")


def ms_of(observed, subs):
    """Self time a step under ``KIND`` and one of ``subs``, or ``None``
    where no instruction of the traced steps stands there."""
    table = device_scopes.table_of(observed)
    if not table:
        return None
    rows = [row[0] for (kind, sub, _), row in table["rows"].items()
            if kind == KIND and sub in subs]
    return sum(rows) if rows else None


def read(observed):
    return ms_of(observed, SUB_SCOPES)
