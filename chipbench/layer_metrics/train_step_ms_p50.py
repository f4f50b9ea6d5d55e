"""Median of the harness span round one trainer step, closed by host_sync."""

from chipbench.arithmetic import median


def read(run):
    xs = run.facts.get("train_step_ms")
    return median(xs) if xs else None
