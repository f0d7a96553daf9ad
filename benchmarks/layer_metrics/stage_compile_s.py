"""Seconds the stager thread spent tracing, lowering and compiling, by
``jitscope``'s counters of that thread (counter ``compile_s`` of
``flash.stage``)."""

from benchmarks import program_spans


def read(observed):
    return program_spans.stage_attr(observed, "compile_s")
