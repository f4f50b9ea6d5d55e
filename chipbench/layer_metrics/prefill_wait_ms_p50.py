"""Median of the program's `engine.prefill_wait` spans: what the host still waits for a prefill round's first tokens once the decode step's tokens are back."""

from chipbench.arithmetic import median
from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    xs = [s.ms for s in spans.get("engine.prefill_wait", [])] if spans else []
    return median(xs) if xs else None
