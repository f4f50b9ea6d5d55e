"""Device self time under the scope `ssm/scan` in the `serve_prefill` program over its runs in the traced slice: the chunked scan of all Mamba-2 layers, a prefill round."""

from chipbench.device_reads import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run, "serve_prefill", "ssm/scan")
