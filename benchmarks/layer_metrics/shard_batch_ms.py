"""Median of the window's ``trainer.shard_batch`` spans: a host batch onto
the mesh."""

from benchmarks import program_spans


def read(observed):
    window = program_spans.select(observed)
    return window and program_spans.median_ms(window.shard_batches)
