"""Device self time a step of the latent-attention layers' core (MLA: the
scores in two products against a head's k_nope and the one rotary key, the
softmax, the product with the values), all passes, on the first chip: the
program's scopes of kind ``attn.core`` with the sub-scope ``latent``
(``ops/attention.py::latent_attention``), from
``benchmarks/device_scopes.py``'s table.  By scope, not by shape: whatever
implements the core is read the same.  Nothing to read where the program
has no such scope (an older commit, another family)."""

from benchmarks import device_scopes

SUB_SCOPE = "latent"


def ms_of(observed, kind, sub=SUB_SCOPE):
    """Self time a step under ``kind`` and ``sub``, or ``None`` where no
    instruction of the traced steps stands there."""
    table = device_scopes.table_of(observed)
    if not table:
        return None
    rows = [row[0] for (k, s, _), row in table["rows"].items()
            if k == kind and s == sub]
    return sum(rows) if rows else None


def read(observed):
    return ms_of(observed, "attn.core")
