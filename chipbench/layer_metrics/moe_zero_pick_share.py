"""Of a decode step's routed picks (real tokens x experts per token, all layers), the share that landed on zero-compute experts and cost no matrix product: `moe_picks_zero` over `moe_picks` on the program's `engine.decode_apply` spans, the median span's. Compute per token follows it: a token brings (1 - share) x experts-per-token expert calls. Nothing where the program counts no such picks."""

from chipbench.arithmetic import median
from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    steps = [s.attrs for s in (spans.get("engine.decode_apply", []) if spans else [])
             if s.attrs.get("moe_picks") and "moe_picks_zero" in s.attrs]
    if not steps:
        return None
    return median(a["moe_picks_zero"] / a["moe_picks"] for a in steps)
