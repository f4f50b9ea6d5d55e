"""Median of the harness span round the loader's next()."""

from chipbench.arithmetic import median


def read(run):
    xs = run.facts.get("data_wait_ms")
    return median(xs) if xs else None
