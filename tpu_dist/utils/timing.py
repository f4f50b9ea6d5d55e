"""Trustworthy device timing for benchmarks.

Per-call host loops do not time the device: dispatch is asynchronous, so
a loop of calls measures the enqueue, and independent iterations may
overlap on the device.  Two rules fix this (see also
`tpu_dist.utils.platform.host_sync`):

1. the timed work must form a DATA-DEPENDENT chain (output n feeds
   input n+1), so the device cannot overlap or cache iterations;
2. the timed region must end with a host readback of a value that
   depends on the result — the bytes cannot reach the host before the
   work is done.
"""

from __future__ import annotations

import time
from typing import Callable

from tpu_dist.utils.platform import host_sync


def bench_chain(step: Callable, x0, iters: int = 20, repeats: int = 3) -> float:
    """Seconds per application of ``step`` (a shape-preserving function),
    measured as ``iters`` chained applications inside ONE compiled
    ``fori_loop`` program, best of ``repeats``."""
    import jax
    from jax import lax

    @jax.jit
    def chain(x):
        return lax.fori_loop(0, iters, lambda i, y: step(y), x)

    host_sync(chain(x0))  # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        host_sync(chain(x0))
        best = min(best, time.perf_counter() - t0)
    return best / iters
