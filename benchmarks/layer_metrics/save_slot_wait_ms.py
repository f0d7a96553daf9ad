"""``flash.save.slot_wait`` of the window's save: dropping a queued copy and
waiting for the one slot of device memory."""

from benchmarks import program_spans


def read(observed):
    return program_spans.save_part_ms(observed, "slot_wait")
