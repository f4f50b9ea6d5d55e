"""Tokens per second times the operations a token requires (forward and backward, attention over the realisable scores, recomputation not credited; counted by the configuration's family) over chips times the bf16 peak."""

from chipbench.arithmetic import mfu_pct, train_flops_per_token


def read(run):
    tps = run.facts.get("train_tokens_per_s")
    if not tps or run.peaks is None:
        return None
    cell = run.cell
    forward = cell.family.forward_flops_per_token(cell.config, run.facts["seq_len"])
    return mfu_pct(tps, train_flops_per_token(forward), cell.chips,
                   run.peaks.bf16_flops_per_s)
