"""Seconds of set-up loading compiled programs from the persistent cache: the program's kept `compile.backend` spans before the window with `cache` hit."""

from chipbench.span_reads import stage_seconds


def read(run):
    return stage_seconds(run, ("compile.backend",), cache=("hit",))
