"""The language models' ``init`` under a kept ``model.init`` span.

A benchmark or a loader may subclass a model and draw the weights its own
way (``class Seeded(HybridLM): def init(...)``), so the span is put round
whatever ``init`` a class of the family defines, when the class is made.
"""

from __future__ import annotations

import functools
import math

import jax

from tpu_dist.observe import compile_spans, spans


def _under_span(init):
    @functools.wraps(init)
    def spanned(self, *args, **kwargs):
        compile_spans.install()  # the draw compiles: its stages nest here
        with spans.span("model.init", keep=True, model=type(self).__name__) as sp:
            out = init(self, *args, **kwargs)
            sp.attrs["params"] = sum(math.prod(a.shape) for a in jax.tree.leaves(out[0]))
        return out

    return spanned


class InitSpan:
    """Mixin: ``init`` returns ``(params, state)`` and is timed as
    ``model.init`` with ``params``, the number of weights drawn.  What the
    span measures is the host's part (tracing, compiling, dispatching);
    the device may still be drawing when it closes."""

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "init" in vars(cls):
            cls.init = _under_span(vars(cls)["init"])
