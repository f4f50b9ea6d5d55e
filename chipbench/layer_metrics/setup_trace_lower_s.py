"""Seconds of set-up in JAX's tracing and lowering: the program's kept `compile.trace` and `compile.lower` spans that ended before the window."""

from chipbench.span_reads import stage_seconds


def read(run):
    return stage_seconds(run, ("compile.trace", "compile.lower"))
