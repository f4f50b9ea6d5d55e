"""Device self time under the state-space mixer's scopes (`ssm/in_proj`, `ssm/conv`, `ssm/scan`, `ssm/gate_norm`, `ssm/out_proj`, `ssm/state_rw`) in the `serve_decode_*` programs over their runs in the traced slice: the Mamba-2 mixers of all layers, a decode step."""

from chipbench.device_reads import runs_ms


def read(run):
    runs = runs_ms(run, "serve_decode")
    if not runs:
        return None
    under = sum(s for name, scope, _, s in run.scopes["by_scope"]
                if name.startswith("serve_decode") and scope.startswith("ssm/"))
    return 1e3 * under / len(runs)
