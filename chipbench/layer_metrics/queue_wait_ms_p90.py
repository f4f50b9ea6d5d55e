"""Due time to admission into a slot, 90th percentile over scored requests."""

from chipbench.arithmetic import percentile


def read(run):
    xs = run.facts.get("queue_wait_ms")
    return percentile(xs, 90) if xs else None
