"""Seconds the stager slept in ``StagePacer.gate`` (counter ``pace_sleep_s``
of ``flash.stage``)."""

from benchmarks import program_spans


def read(observed):
    return program_spans.stage_attr(observed, "pace_sleep_s")
