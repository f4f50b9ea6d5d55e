"""Device self time of the dense feed-forwards (scope `mlp`) in the `serve_decode_*` programs over their runs in the traced slice, a decode step: weight reads a token cannot avoid, beside the routed branch's."""

from chipbench.device_reads import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run, "serve_decode", "mlp")
