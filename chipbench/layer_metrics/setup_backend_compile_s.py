"""Seconds of set-up in XLA's compile: the program's kept `compile.backend` spans before the window that the persistent cache did not serve (`cache` miss or off)."""

from chipbench.span_reads import stage_seconds


def read(run):
    return stage_seconds(run, ("compile.backend",), cache=("miss", "off"))
