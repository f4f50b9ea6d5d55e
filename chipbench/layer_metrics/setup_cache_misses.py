"""Programs set-up compiled and wrote to the persistent cache (`compile.backend` spans with `cache` miss): 0 in a run that started warm."""

from chipbench.span_reads import cache_misses as read  # noqa: F401
