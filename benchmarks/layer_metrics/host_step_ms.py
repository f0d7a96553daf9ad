"""Median of the window's ``trainer.step`` spans: all of
``Trainer.train_step`` on the host, the jitted call's dispatch included."""

from benchmarks import program_spans


def read(observed):
    window = program_spans.select(observed)
    return window and program_spans.median_ms(window.steps)
