"""Seconds placing parameters and optimizer state (the program's kept `trainer.place_state` span, a part of `trainer.init`) less the compile stages inside it."""

from chipbench.span_reads import phase_seconds


def read(run):
    return phase_seconds(run, "trainer.place_state")
