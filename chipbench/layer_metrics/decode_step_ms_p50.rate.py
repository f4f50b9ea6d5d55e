"""Median engine step that decoded and ran no prefill round."""

from chipbench.arithmetic import median


def read(run):
    xs = run.facts.get("decode_step_ms")
    return median(xs) if xs else None
