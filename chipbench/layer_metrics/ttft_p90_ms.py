"""90th percentile over scored requests of due time to first token: the tail beside the mean, which is the end-to-end metric (an order statistic of 188 requests spreads by 4 % from run to run, PERF.md section 2)."""

from chipbench.arithmetic import percentile


def read(run):
    xs = run.facts.get("ttft_ms")
    return percentile(xs, 90) if xs else None
