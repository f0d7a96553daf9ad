"""Seconds the stager waited in ``np.asarray`` for a chunk to come from the
device (counter ``d2h_wait_s`` of ``flash.stage``)."""

from benchmarks import program_spans


def read(observed):
    return program_spans.stage_attr(observed, "d2h_wait_s")
