"""Of the positions the windowed layers would attend with no window (`swa_rows_unwindowed`: for every real query and windowed layer, the positions its request holds), the share they do attend in their rings (`swa_rows_attended`: at most the window a query): the program's own counts, off the median `engine.decode_apply` span's attrs. 1 while every context is within the window."""

from chipbench.arithmetic import median
from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    steps = [s.attrs for s in (spans.get("engine.decode_apply", []) if spans else [])
             if s.attrs.get("swa_rows_unwindowed")]
    if not steps:
        return None
    return median(a["swa_rows_attended"] / a["swa_rows_unwindowed"] for a in steps)
