"""``flash.save.device_copy`` of the window's save: dispatching the copy of
every leaf on the device."""

from benchmarks import program_spans


def read(observed):
    return program_spans.save_part_ms(observed, "device_copy")
