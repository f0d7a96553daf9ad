"""Device self time a step of the attention under the block-diffusion mask,
all passes, on the first chip: the program's scopes of kind ``attn.core``
with the sub-scopes that attention has and no other does (``bd_keys``: the
joining of a noisy block's clean and noisy keys and the mask from ``iota``;
``bd_clean`` and ``bd_noisy``: the two halves' attention, kernels and
copies), from ``benchmarks/device_scopes.py``'s table.  By scope, not by
shape: whatever implements the attention is read the same.  Nothing to read
where the program has no such scope (an older commit, another family)."""

from benchmarks import device_scopes

KIND = "attn.core"
SUB_SCOPES = ("bd_keys", "bd_clean", "bd_noisy")


def ms_of(observed, kind, subs):
    """Self time a step under ``kind`` and one of ``subs``, or ``None``
    where no instruction of the traced steps stands there."""
    table = device_scopes.table_of(observed)
    if not table:
        return None
    rows = [row[0] for (k, sub, _), row in table["rows"].items()
            if k == kind and sub in subs]
    return sum(rows) if rows else None


def read(observed):
    return ms_of(observed, KIND, SUB_SCOPES)
