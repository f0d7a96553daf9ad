"""The ``flash.stage`` span of the window's save: the stager thread, from
taking the copy to the snapshot committed in shm."""

from benchmarks import program_spans


def read(observed):
    window = program_spans.select(observed)
    if not window or window.stage is None:
        return None
    return program_spans.dur_ns(window.stage) * 1e-9
