"""Median over scored requests of each request's own mean gap between tokens: the median beside the mean and the 90th percentile."""

from chipbench.arithmetic import median


def read(run):
    xs = run.facts.get("tpot_ms")
    return median(xs) if xs else None
