"""Device self time under the scope `ssm/state_rw` in the `serve_decode_*` programs over their runs in the traced slice: reading the slots' recurrent state and writing it back, where that is not fused into the scan."""

from chipbench.device_reads import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run, "serve_decode", "ssm/state_rw")
