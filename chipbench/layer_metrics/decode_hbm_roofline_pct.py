"""Least time the memory system could take for a decode step (every weight once, plus the keys and values of the tokens the active slots hold, plus each busy slot's recurrent state read and written where the family has one; counted by the configuration's family) over the median decode-only step. Memory bound."""

import jax.numpy as jnp

from chipbench.arithmetic import decode_step_bytes, hbm_roofline_pct, median


def read(run):
    ms = run.facts.get("decode_step_ms")
    if not ms or run.peaks is None:
        return None
    cell = run.cell
    width = jnp.dtype(cell.config["serve"]["dtype"]).itemsize
    state = getattr(cell.family, "state_bytes_per_slot", None)
    step = decode_step_bytes(
        cell.family.param_count(cell.config) * width,
        median(run.facts["decode_held_tokens"]),
        cell.family.kv_bytes_per_token(cell.config, width),
        median(run.facts["decode_busy_slots"]),
        state(cell.config) if state else 0,
    )
    return hbm_roofline_pct(step, median(ms) / 1e3, run.peaks.hbm_bytes_per_s)
