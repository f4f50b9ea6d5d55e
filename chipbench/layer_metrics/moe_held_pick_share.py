"""Of the routed picks the window's programs counted (real tokens x experts per token, all layers), the share that landed on the experts held here: `moe_picks_held` over `moe_picks`, from the attrs of the program's `engine.decode_apply` spans."""

from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    steps = spans.get("engine.decode_apply", []) if spans else []
    picks = sum(s.attrs.get("moe_picks", 0) for s in steps)
    if not picks:
        return None
    return sum(s.attrs["moe_picks_held"] for s in steps) / picks
