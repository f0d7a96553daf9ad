"""The rows this chip's experts took over the rows a fair share would take
(tokens x experts a token x held / all), from the program's own counter
(``share_rows_over_expected`` in the window's ``trainer.model_stats``
records: the worst layer of the worst record).  1 is an even routing; the
ladder's first extent holds 1.25."""

from benchmarks import program_spans


def read(observed):
    records = program_spans.model_stats(observed, "share_rows_over_expected")
    if not records:
        return None
    return max(max(layers) for _, layers in records)
