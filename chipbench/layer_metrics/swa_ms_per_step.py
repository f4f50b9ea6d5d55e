"""Device self time under the windowed latent layers' own scopes (`swa/ring_rw`: the write into and the read of each slot's ring; `swa/attend`: absorbed attention over the ring) in the `serve_decode_*` programs over their runs in the traced slice, a decode step."""

from chipbench.device_reads import scope_ms_per_run

SCOPES = ('swa/ring_rw', 'swa/attend')


def read(run):
    parts = [scope_ms_per_run(run, "serve_decode", scope) for scope in SCOPES]
    return None if None in parts else sum(parts)
