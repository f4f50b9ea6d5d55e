"""Median device duration of one run of a `serve_decode_*` program in the traced slice (the `XLA Modules` line): the inside twin of `decode_step_ms_p50.*`, which times the whole `engine.step()` from outside."""

from chipbench.device_reads import median_run_ms


def read(run):
    return median_run_ms(run, "serve_decode")
