"""JAX's compile pipeline on the span ring, by program name.

JAX hands every stage of a compilation to `jax.monitoring` with the name
of the function it compiles: the trace to a jaxpr, the lowering to a
module, and the backend's part, which on JAX 0.9 wraps
``compiler.compile_or_get_cached`` and so is XLA's compile OR the load of
a compiled program from the persistent cache, with the cache's own events
(hit, miss, retrieval time) fired inside it.  `install` listens, and each
stage becomes a kept span (`spans.record(..., keep=True, nest=True)`):

==================  =====================================================
``compile.trace``   ``fun``
``compile.lower``   ``fun``
``compile.backend`` ``fun``; ``cache`` = ``hit`` (loaded: ``load_s`` the
                    retrieval, ``saved_s`` the compile time the cache's
                    entry remembers less that), ``miss`` (compiled and
                    written: the next process hits) or ``off`` (compiled
                    and not written: no cache, or a program under the
                    cache's thresholds, compiled by every process)
==================  =====================================================

``fun`` is the jitted function's name (``train_step``, ``serve_prefill``),
JAX's ``jit(...)`` round it taken off.  The span's end is
`time.perf_counter` read in the callback, which JAX makes as the stage
ends, and its start lies the reported duration before: the ring's one
clock, whatever clock JAX timed with.  Its parent is the span open on the
calling thread, so a step that recompiles carries the stage as a child.

JAX traces the jitted functions a program calls inside the program's own
trace, thousands of them for a deep model, and reports each.  Only the
OUTERMOST stage on a thread becomes a span; what runs inside it is part of
it.  The spans of one thread therefore never overlap, and their durations
add up to the time the thread spent in the pipeline, as do the seconds of
``tpu_dist_compile_seconds_total{stage}``.
"""

from __future__ import annotations

import threading
import time

from tpu_dist.observe import events, spans
from tpu_dist.observe.registry import REGISTRY

STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
}
_CACHE = {
    "/jax/compilation_cache/cache_hits": "hit",
    "/jax/compilation_cache/cache_misses": "miss",
}
_CACHE_SECONDS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "load_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}

_seconds = REGISTRY.counter(
    "tpu_dist_compile_seconds_total",
    "seconds in JAX's compile pipeline by stage: trace, lower, backend "
    "(XLA's compile or the load from the persistent cache)",
)
_outcomes = {
    "hit": REGISTRY.counter(
        "tpu_dist_compile_cache_hits_total",
        "XLA programs loaded from the persistent compilation cache",
    ),
    "miss": REGISTRY.counter(
        "tpu_dist_compile_cache_misses_total",
        "XLA programs compiled and written to the persistent cache",
    ),
}


class _Thread(threading.local):
    depth = 0     # stages open on this thread
    cache = None  # what the cache has said since the last backend stage ended


_thread = _Thread()


def _on_enter(event: str, value, **kw) -> None:
    """JAX reports a stage's start as a scalar under the stage's name."""
    if event in STAGES:
        _thread.depth += 1


def _on_cache(event: str, **kw) -> None:
    outcome = _CACHE.get(event)
    if outcome is None:
        return
    _thread.cache = {"cache": outcome}
    _outcomes[outcome].inc()
    import jax

    events.from_env().emit(
        "compile_cache", outcome=outcome, dir=jax.config.jax_compilation_cache_dir
    )


def _on_duration(event: str, seconds: float, **kw) -> None:
    stage = STAGES.get(event)
    if stage is None:
        key = _CACHE_SECONDS.get(event)
        if key is not None and _thread.cache is not None:
            _thread.cache[key] = seconds
        return
    cache = None
    if stage == "backend":
        cache, _thread.cache = _thread.cache, None
    depth = _thread.depth = max(_thread.depth - 1, 0)
    if depth:
        return  # inside another stage: that stage's span holds this time
    end = time.perf_counter()
    attrs = {"fun": _fun(str(kw.get("fun_name", "")))}
    if stage == "backend":
        attrs.update(cache or {"cache": "off"})
    _seconds.inc(seconds, stage=stage)
    spans.record(f"compile.{stage}", end - seconds, end, keep=True, nest=True, **attrs)


def _fun(name: str) -> str:
    """``jit(train_step)`` -> ``train_step``."""
    if name.endswith(")") and "(" in name:
        return name[name.index("(") + 1: -1]
    return name


def install() -> None:
    """Listen to JAX's compile pipeline.  Idempotent, and repairs itself:
    a listener is registered where it is not (``jax.monitoring``'s
    ``clear_event_listeners`` takes away everyone's).  Called when the
    package is imported, by `utils.platform.setup_compile_cache`, and
    before a model's weights are drawn and a trainer or an engine is
    built, so that no path compiles unheard."""
    from jax._src import monitoring  # the public module has no getters

    if _on_enter not in monitoring.get_scalar_listeners():
        monitoring.register_scalar_listener(_on_enter)
    if _on_cache not in monitoring.get_event_listeners():
        monitoring.register_event_listener(_on_cache)
    if _on_duration not in monitoring.get_event_duration_listeners():
        monitoring.register_event_duration_secs_listener(_on_duration)
