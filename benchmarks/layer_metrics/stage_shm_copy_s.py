"""Seconds the stager spent copying chunks into the shm segment (counter
``shm_copy_s`` of ``flash.stage``)."""

from benchmarks import program_spans


def read(observed):
    return program_spans.stage_attr(observed, "shm_copy_s")
