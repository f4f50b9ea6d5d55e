"""Device self time of the selecting layers' sparse read in the `serve_decode_*` programs over their runs in the traced slice, a decode step: the scopes `dsa/gather` (the selected latent rows fetched through the block table) and `mla/attend` (absorbed attention over the fetched rows)."""

from chipbench.device_reads import scope_ms_per_run

SCOPES = ('dsa/gather', 'mla/attend')


def read(run):
    parts = [scope_ms_per_run(run, "serve_decode", scope) for scope in SCOPES]
    return None if None in parts else sum(parts)
