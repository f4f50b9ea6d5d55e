"""Of the positions the selecting layers scored in the window (`dsa_keys_scored`: for every real query and selecting layer, the positions its slot holds), the share whose latent row was then read (`dsa_rows_selected`: at most `index_topk` a query): the programs' own counts, from the attrs of the program's `engine.decode_apply` spans. 100 while every context is within `index_topk`."""

from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    steps = spans.get("engine.decode_apply", []) if spans else []
    scored = sum(s.attrs.get("dsa_keys_scored", 0) for s in steps)
    if not scored:
        return None
    return 100.0 * sum(s.attrs["dsa_rows_selected"] for s in steps) / scored
