"""The median chunk the stager moved, in MiB (counter ``chunk_bytes_median``
of ``flash.stage``): what the pacer settled on."""

from benchmarks import program_spans


def read(observed):
    value = program_spans.stage_attr(observed, "chunk_bytes_median")
    return None if value is None else value / 2 ** 20
