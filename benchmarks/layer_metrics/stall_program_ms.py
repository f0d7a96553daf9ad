"""The part of ``stall_ms`` that the program's ``flight_recorder.explain``
puts under spans of the stepping thread, or that the collector's pauses
(``gc_ns``) hold: what a change to the program can shorten."""

from benchmarks import step_ledger


def read(observed):
    return step_ledger.metric(observed, step_ledger.stall_program_ms)
