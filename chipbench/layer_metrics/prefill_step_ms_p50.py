"""Median engine step that ran a prefill round (with or without a decode step beside it)."""

from chipbench.arithmetic import median


def read(run):
    xs = run.facts.get("prefill_step_ms")
    return median(xs) if xs else None
