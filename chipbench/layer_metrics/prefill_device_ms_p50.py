"""Median device duration of one run of `serve_prefill` in the traced slice (the `XLA Modules` line): the inside twin of `prefill_step_ms_p50`, which times a whole `engine.step()` that held a prefill round, decode included."""

from chipbench.device_reads import median_run_ms


def read(run):
    return median_run_ms(run, "serve_prefill")
