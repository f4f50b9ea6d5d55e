"""Median host time of an engine step: the program's `engine.step` span less its `engine.decode_wait` and `engine.prefill_wait` children."""

from chipbench.program_spans import engine_host_ms_p50 as read  # noqa: F401
