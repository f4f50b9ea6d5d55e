"""Device self time under the scope `attn/kv_gather` in the `serve_decode_*` programs over their runs in the traced slice: the gather of the held blocks, the split into heads and `_expand_kv`, a decode step."""

from chipbench.device_reads import scope_ms_per_run


def read(run):
    return scope_ms_per_run(run, "serve_decode", "attn/kv_gather")
