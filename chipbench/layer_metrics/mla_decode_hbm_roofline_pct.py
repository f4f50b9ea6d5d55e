"""Least time the memory system could take for a decode step of a model whose attention reads every latent row its slots hold and whose held experts are read only where a token picked them, over the median decode-only step as the harness times it: every held weight outside the routed experts once, a routed expert's only if the step gave it a token (`moe_experts_hit`), a latent row for every position a sublayer attended (`mla_rows_attended`): the family's `decode_required_bytes` of the program's own counts, the median `engine.decode_apply` span's. The least bytes, so it cannot pass 100. Memory bound."""

import jax.numpy as jnp

from chipbench.arithmetic import hbm_roofline_pct, median
from chipbench.program_spans import window_spans

COUNTS = ("moe_experts_hit", "mla_rows_attended")


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
