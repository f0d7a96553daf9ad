"""The counter that picks the expert layer's extent, from the program's own
records: the hottest chip's live rows over the chips' mean
(``chip_rows_max_over_mean`` in the ``trainer.model_stats`` spans the
window's steps left in the recorder), read as ``moe_rows_held_over_live``
reads its name: the worst layer of the worst record.  1 is every chip
holding a quarter of the assignments under ``ep=4``; the ladder's first
extent holds 1.25 times the rows expected on a chip, so a cell that reads
well under 1.25 here and 1.25 in ``moe_rows_held_over_live`` is on its
first extent in every pass, and the ledger shows PR by PR whether it still
is.  ``moe_rows_held_over_live`` prints the records layer by layer."""

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "chip_rows_max_over_mean")
    return max(max(layers) for _, layers in records) if records else None
