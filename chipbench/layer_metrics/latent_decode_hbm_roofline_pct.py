"""Least time the memory system could take for a decode step of a model whose attention reads a selection and a window and whose held experts are read only where a token picked them, over the median decode-only step: every held weight once but a routed expert's only if the step gave it a token (`moe_experts_hit`), an index key for every position a selecting layer scored, a latent row for every row it selected, a ring row for every position a windowed layer attended (the family's `decode_required_bytes` of the program's own counts, the median `engine.decode_apply` span's). Unlike `decode_hbm_roofline_pct` it does not charge every held token its whole cache row, which this decode does not have to read. Memory bound."""

import jax.numpy as jnp

from chipbench.arithmetic import hbm_roofline_pct, median
from chipbench.program_spans import window_spans

COUNTS = ("moe_experts_hit", "dsa_keys_scored", "dsa_rows_selected", "swa_rows_attended")


def read(run):
    ms = run.facts.get("decode_step_ms")
    required = getattr(run.cell.family, "decode_required_bytes", None)
    if not ms or run.peaks is None or required is None:
        return None
    spans = window_spans(run)
    steps = [s.attrs for s in (spans.get("engine.decode_apply", []) if spans else [])
             if all(name in s.attrs for name in COUNTS)]
    if not steps:
        return None
    width = jnp.dtype(run.cell.config["serve"]["dtype"]).itemsize
    step = median(required(run.cell.config, {name: a[name] for name in COUNTS}, width)
                  for a in steps)
    return hbm_roofline_pct(step, median(ms) / 1e3, run.peaks.hbm_bytes_per_s)
