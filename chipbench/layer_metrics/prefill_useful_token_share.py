"""Prompt tokens over token places (rows x chunk) of the window's prefill rounds, from the attrs of the program's `engine.prefill_dispatch` spans."""

from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    rounds = spans.get("engine.prefill_dispatch", []) if spans else []
    places = sum(s.attrs["rows"] * s.attrs["chunk"] for s in rounds)
    if not places:
        return None
    return 100.0 * sum(s.attrs["real_tokens"] for s in rounds) / places
