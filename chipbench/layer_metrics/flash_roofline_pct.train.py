"""Operations attention requires in the slice's `train_step` runs (forward over the realisable scores, backward at twice that; the recomputation inside the kernels not credited; counted by the configuration's family) over the device time of the `flash_*` kernels on the first chip, as a share of the bf16 peak. Compute bound."""

from chipbench.arithmetic import train_flops_per_token
from chipbench.device_reads import kernel_seconds, runs_ms


def read(run):
    steps = runs_ms(run, "train_step")
    if not steps or run.peaks is None:
        return None
    seconds = kernel_seconds(run, "flash_")
    if not seconds:
        return None  # a share of a peak is never reported as 0
    cell = run.cell
    per_token = cell.family.attention_flops_per_token(cell.config, run.facts["seq_len"])
    # the table is the first chip's, which holds its share of the step's rows
    tokens = len(steps) * cell.config["train"]["batch_tokens"] / cell.chips
    flops = tokens * train_flops_per_token(per_token)
    return 100.0 * flops / seconds / run.peaks.bf16_flops_per_s
