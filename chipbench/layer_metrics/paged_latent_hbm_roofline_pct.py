"""The kernel `paged_latent_decode`'s share of its roofline: the bytes it has to read in a decode step (ONE latent row, the family's `attended_row_bytes`, for every position the step's queries attend: `mla_rows_attended`, the program's own count, off the median `engine.decode_apply` span's attrs; the row is key and value both and is charged once) over the HBM's speed, over the kernel's device seconds a `serve_decode_*` run in the traced slice. Memory bound (64 heads on a 1,280-byte row: ~109 operations a byte, under the v5e's ridge). Nothing, never 0, where the kernel holds no time."""

import jax.numpy as jnp

from chipbench.arithmetic import hbm_roofline_pct, median
from chipbench.device_reads import kernel_seconds, runs_ms
from chipbench.program_spans import window_spans


def read(run):
    runs = runs_ms(run, "serve_decode")
    row = getattr(run.cell.family, "attended_row_bytes", None)
    if not runs or run.peaks is None or row is None:
        return None
    seconds = kernel_seconds(run, "paged_latent_decode")
    if not seconds:
        return None
    spans = window_spans(run)
    steps = [s.attrs["mla_rows_attended"]
             for s in (spans.get("engine.decode_apply", []) if spans else [])
             if "mla_rows_attended" in s.attrs]
    if not steps:
        return None
    width = jnp.dtype(run.cell.config["serve"]["dtype"]).itemsize
    step = row(run.cell.config, width) * median(steps)
    return hbm_roofline_pct(step, seconds / len(runs), run.peaks.hbm_bytes_per_s)
