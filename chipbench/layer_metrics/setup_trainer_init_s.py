"""Seconds of `LMTrainer`'s construction (the program's kept `trainer.init` span) less the compile stages inside it."""

from chipbench.span_reads import phase_seconds


def read(run):
    return phase_seconds(run, "trainer.init")
