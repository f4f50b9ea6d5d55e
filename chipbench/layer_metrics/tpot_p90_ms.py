"""90th percentile over scored requests of each request's own mean gap between tokens: the tail beside the mean, which is the end-to-end metric (the per-request gaps are lumpy, so this order statistic flips between 195 and 205 ms from run to run, PERF.md section 2)."""

from chipbench.arithmetic import percentile


def read(run):
    xs = run.facts.get("tpot_ms")
    return percentile(xs, 90) if xs else None
