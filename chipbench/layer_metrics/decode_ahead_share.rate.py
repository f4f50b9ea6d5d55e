"""Share of the window's `engine.decode_dispatch` spans with `ahead` true: decode steps launched while the step before was still unread."""

from chipbench.span_reads import decode_ahead_share as read  # noqa: F401
