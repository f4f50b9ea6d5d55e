"""Device self time under the scope `attn/scores` in the `serve_decode_*` programs over their runs in the traced slice: scores, mask, softmax and the weighted sum, a decode step."""

from chipbench.device_reads import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run, "serve_decode", "attn/scores")
