"""Median over scored requests of due time to first token: the median beside the mean and the 90th percentile."""

from chipbench.arithmetic import median


def read(run):
    xs = run.facts.get("ttft_ms")
    return median(xs) if xs else None
