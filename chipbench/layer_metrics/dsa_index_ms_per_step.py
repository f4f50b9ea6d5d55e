"""Device self time of the key selection in the `serve_decode_*` programs over their runs in the traced slice, a decode step: the scopes `dsa/index` (the indexer's queries, weights and key, the gather of the slot's held index keys, the scores) and `dsa/topk` (`lax.top_k` of the scores), the selecting layers of all depths together."""

from chipbench.device_reads import scope_ms_per_run

SCOPES = ('dsa/index', 'dsa/topk')


def read(run):
    parts = [scope_ms_per_run(run, "serve_decode", scope) for scope in SCOPES]
    return None if None in parts else sum(parts)
