"""Request front-end over the continuous-batching engine.

`LMServer` is the deployment-shaped surface: construct it from live
params or from a weight artifact on disk (`export.save_params` /
`load_params` — the raw-weights counterpart of the sealed
`export_generate` artifact, see export.py's docstring for when each is
right), `submit()` requests with per-request sampling params, and
drive the engine with `step()` / `run_until_drained()`.  Telemetry
flows through the engine (`TPU_DIST_TELEMETRY` request-lifecycle
events, Prometheus gauges on ``TPU_DIST_METRICS_PORT``), so a served
process is observable with the same `tools/tpu_top.py` dashboard as a
training run.
"""

from __future__ import annotations

import time

from tpu_dist.serve.engine import (
    RequestResult,
    SamplingParams,
    ServeConfig,
    ServeEngine,
)


class LMServer:
    """One model, one paged KV pool, one admission queue."""

    def __init__(self, lm, params, config: ServeConfig | None = None, *,
                 now=time.perf_counter, events=None):
        self.lm = lm
        self.engine = ServeEngine(
            lm, params, config, now=now, events=events
        )

    @classmethod
    def from_artifact(cls, lm, path, config: ServeConfig | None = None,
                      *, init_key=None, **kw) -> "LMServer":
        """Load raw weights saved with `export.save_params` (the server
        keeps sampling a RUNTIME concern — per request — instead of
        serving a sealed `export_generate` artifact whose sampling
        config is frozen at export time)."""
        import jax

        from tpu_dist import export

        # restore only needs the tree STRUCTURE — eval_shape gives it
        # without materializing a throwaway set of random weights
        like, _ = jax.eval_shape(
            lm.init,
            init_key if init_key is not None else jax.random.key(0),
        )
        params = export.load_params(path, like)
        return cls(lm, params, config, **kw)

    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int | None = None,
               top_p: float | None = None, seed: int = 0,
               stop_token: int | None = None) -> int:
        """Queue a request; returns its id (see `result`)."""
        return self.engine.submit(
            prompt, max_new_tokens,
            sampling=SamplingParams(
                temperature=temperature, top_k=top_k, top_p=top_p,
                seed=seed,
            ),
            stop_token=stop_token,
        )

    def cancel(self, request_id: int) -> bool:
        return self.engine.cancel(request_id)

    def step(self) -> None:
        self.engine.step()

    def run_until_drained(self, **kw) -> dict[int, RequestResult]:
        return self.engine.run_until_drained(**kw)

    def result(self, request_id: int) -> RequestResult | None:
        return self.engine.results.get(request_id)

    @property
    def pending(self) -> bool:
        return self.engine.pending
