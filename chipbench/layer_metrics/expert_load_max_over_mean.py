"""Tokens the busiest held expert was given in the window over the mean of the held experts (all layers summed): `moe_expert_tokens`, from the attrs of the program's `engine.decode_apply` spans. 1 is an even load."""

from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    steps = spans.get("engine.decode_apply", []) if spans else []
    loads = [sum(col) for col in zip(*(s.attrs["moe_expert_tokens"] for s in steps
                                       if "moe_expert_tokens" in s.attrs))]
    if not loads or not sum(loads):
        return None
    return max(loads) * len(loads) / sum(loads)
