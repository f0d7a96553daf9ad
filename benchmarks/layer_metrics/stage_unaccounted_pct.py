"""``flash.stage`` less its lock wait, sleeps, slicing, waits for the device
and copies into shm, over ``flash.stage``: what the stager's counters do
not see."""

from benchmarks import program_spans


def read(observed):
    window = program_spans.select(observed)
    if not window or window.stage is None:
        return None
    return program_spans.unaccounted_pct(window.stage)
