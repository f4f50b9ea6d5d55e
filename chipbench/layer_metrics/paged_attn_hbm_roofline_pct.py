"""The kernel `paged_attn_decode`'s share of its roofline: the bytes it has to read in a decode step (a key and a value, the family's `attended_row_bytes`, for every position the step's queries attend through it: `attn_rows_attended` in the pool and `swa_rows_attended` in the rings, the program's own counts, off the median `engine.decode_apply` span's attrs) over the HBM's speed, over the kernel's device seconds a `serve_decode_*` run in the traced slice. Memory bound. Nothing, never 0, where the kernel holds no time."""

import jax.numpy as jnp

from chipbench.arithmetic import hbm_roofline_pct, median
from chipbench.device_reads import kernel_seconds, runs_ms
from chipbench.program_spans import window_spans

COUNTS = ("attn_rows_attended", "swa_rows_attended")


def read(run):
    runs = runs_ms(run, "serve_decode")
    row = getattr(run.cell.family, "attended_row_bytes", None)
    if not runs or run.peaks is None or row is None:
        return None
    seconds = kernel_seconds(run, "paged_attn_decode")
    if not seconds:
        return None
    spans = window_spans(run)
    steps = [s.attrs for s in (spans.get("engine.decode_apply", []) if spans else [])
             if all(name in s.attrs for name in COUNTS)]
    if not steps:
        return None
    width = jnp.dtype(run.cell.config["serve"]["dtype"]).itemsize
    step = row(run.cell.config, width) * median(sum(a[name] for name in COUNTS) for a in steps)
    return hbm_roofline_pct(step, seconds / len(runs), run.peaks.hbm_bytes_per_s)
