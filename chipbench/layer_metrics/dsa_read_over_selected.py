"""Rows of the latent pool the selecting layers' reads fetched in the window for each row they selected (`dsa_rows_read` over `dsa_rows_selected`): the programs' own counts, from the attrs of the program's `engine.decode_apply` spans. A decode call fetches its picks one by one where its contexts are long against `index_topk` (rows read = rows selected) and reads every held row under the picks' mask where they are not (rows read = rows held): 1.0 while every step fetched, the cell's held over selected while every step read its pool in place. Nothing where the program does not count its reads."""

from chipbench.program_spans import window_spans


def read(run):
    spans = window_spans(run)
    steps = [s.attrs for s in (spans.get("engine.decode_apply", []) if spans else [])
             if "dsa_rows_read" in s.attrs]
    selected = sum(a["dsa_rows_selected"] for a in steps)
    if not selected:
        return None
    return sum(a["dsa_rows_read"] for a in steps) / selected
