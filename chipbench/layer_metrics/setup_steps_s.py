"""Seconds of the harness's step spans before the window (check steps, the closed loop's fill) less the program's kept compile stages inside them: set-up spent running the programs."""

from chipbench.span_reads import steps_seconds as read  # noqa: F401
