"""How late the open-loop generator submitted a request: submitted - due, 99th percentile."""

from chipbench.arithmetic import percentile


def read(run):
    xs = run.facts.get("generator_late_ms")
    return percentile(xs, 99) if xs else None
