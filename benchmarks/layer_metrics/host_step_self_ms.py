"""Median of ``trainer.step`` less its ``trainer.step.dispatch``: the
bookkeeping around the jitted call (step clock, flight recorder, goodput
ledger, digest file, reshard and demotion polls)."""

from benchmarks import program_spans


def read(observed):
    window = program_spans.select(observed)
    if not window:
        return None
    value = program_spans.median([
        program_spans.dur_ns(s) - program_spans.dur_ns(window.dispatch[s.span_id])
        for s in window.steps if s.span_id in window.dispatch])
    return None if value is None else value * 1e-6
