"""The yardstick's arithmetic: percentiles, shares of a peak.

Everything here is computed from readings the harness took and from the
counts a configuration's family gives (`chipbench/families/<name>.py`:
parameters, operations per token, cache bytes per token, state bytes per
slot); no function
imports the program.  Counts are what the algorithm requires: recomputed
operations (rematerialisation, the flash kernels' second pass over the
scores) are not credited.
"""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    order statistics, as numpy's default does."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def train_flops_per_token(forward_flops_per_token: float) -> float:
    """Forward plus backward (twice the forward's products)."""
    return 3.0 * forward_flops_per_token


def mfu_pct(tokens_per_s: float, flops_per_token: float, chips: int,
            peak_flops_per_s: float) -> float:
    return 100.0 * tokens_per_s * flops_per_token / (chips * peak_flops_per_s)


def decode_step_bytes(weight_bytes: float, held_tokens: float, kv_bytes_per_token: float,
                      busy_slots: float = 0.0, state_bytes_per_slot: float = 0.0) -> float:
    """Bytes one decode step has to move: every weight once, the keys and
    values of the tokens the active slots really hold, and, where the
    architecture keeps a recurrent state per slot, each busy slot's state
    read once and written once."""
    return (weight_bytes + held_tokens * kv_bytes_per_token
            + 2.0 * busy_slots * state_bytes_per_slot)


def hbm_roofline_pct(step_bytes: float, step_seconds: float,
                     hbm_bytes_per_s: float) -> float:
    """Least time the memory system could take, over the time taken."""
    return 100.0 * (step_bytes / hbm_bytes_per_s) / step_seconds
