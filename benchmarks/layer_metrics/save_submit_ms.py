"""``flash.save.submit`` of the window's save: handing the copy to the stager
thread."""

from benchmarks import program_spans


def read(observed):
    return program_spans.save_part_ms(observed, "submit")
