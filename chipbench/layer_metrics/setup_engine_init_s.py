"""Seconds of `ServeEngine`'s construction (the program's kept `engine.init` span) less the compile stages inside it."""

from chipbench.span_reads import phase_seconds


def read(run):
    return phase_seconds(run, "engine.init")
