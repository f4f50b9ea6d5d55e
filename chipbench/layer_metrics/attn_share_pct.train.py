"""Share of `train_step`'s device self time under the scope `block/attn` (forward, backward and rematerialised), first chip, over the traced slice."""

from chipbench.device_reads import scope_share_pct


def read(run):
    return scope_share_pct(run, "train_step", "block/attn")
