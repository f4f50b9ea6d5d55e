"""Arrival in the engine's queue to admission into a slot: the program's `request.queued` spans of requests admitted in the window, 90th percentile."""

from chipbench.arithmetic import percentile
from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    xs = [s.ms for s in spans.get("request.queued", [])] if spans else []
    return percentile(xs, 90) if xs else None
