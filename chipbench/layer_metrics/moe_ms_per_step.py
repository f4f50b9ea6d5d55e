"""Device self time of the expert layer in the `serve_decode_*` programs over their runs in the traced slice, a decode step: the scopes `moe/router`, `moe/sort`, `moe/experts`, `moe/combine`, `moe/shared`, PLUS the programs' unscoped time. The grouped product (`lax.ragged_dot` under `moe/experts`) becomes the TPU compiler's own `ragged-dot` call, which keeps no `op_name`, so the table files it under `(unscoped)` with the few copies the compiler adds; in these programs nothing else is unscoped (PERF.md sections 5 and 7)."""

from chipbench.device_reads import runs_ms
from chipbench.scopes import UNSCOPED


def read(run):
    runs = runs_ms(run, "serve_decode")
    if not runs:
        return None
    under = sum(s for name, scope, _, s in run.scopes["by_scope"]
                if name.startswith("serve_decode") and (scope.startswith("moe/") or scope == UNSCOPED))
    return 1e3 * under / len(runs)
