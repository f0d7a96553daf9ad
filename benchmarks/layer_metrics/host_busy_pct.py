"""The stepping thread's time under ``trainer.*`` and ``flash.save`` over the
time the window's steps span: how near the host is to setting the pace."""

from benchmarks import program_spans


def read(observed):
    window = program_spans.select(observed)
    return window and program_spans.busy_pct(window)
