"""Continuous-batching decode engine — admit/evict at STEP granularity.

`TransformerLM.generate` is static batching: a fixed batch enters
together, every stream runs the full step count, and a finished stream
burns its slot until the longest one ends.  Under mixed output lengths
that is the serving throughput cliff (most of the batch is padding most
of the time).  This engine is the standard fix:

- a fixed number of DECODE SLOTS (``max_batch``) backed by the paged KV
  pool (`serve.paged_kv`) — blocks allocated at admission, freed at
  eviction;
- a step loop that, EVERY step, evicts finished requests, admits queued
  ones into the freed slots (FIFO; head-of-line blocks on pool
  exhaustion, so admission order is deterministic), launches one batched
  DECODE step over every active slot and at most one chunked PREFILL
  round (prompt ingestion never stalls in-flight decodes for more than
  one chunk), and only then reads the decode step launched the call
  BEFORE: a look-ahead of one, so the chip runs step n+1 while the host
  keeps its books for step n (`ServeEngine.step`);
- per-request sampling params (`sample_slots` — temperature/top_k/top_p
  are per-slot runtime values, so one compiled step program serves any
  request mix), per-request PRNG streams keyed by (seed, token index);
- request-lifecycle telemetry: ``request_admit`` / ``prefill`` /
  ``decode_step`` / ``request_finish`` events (`observe.events`
  schema), occupancy / queue-depth / KV-pool gauges, cumulative
  counters and TTFT / TPOT histograms in `observe.registry.REGISTRY`;
- spans in `observe.spans`' ring: one ``engine.step`` per call with its
  phases as children (``engine.admit``, ``.decode_dispatch``,
  ``.prefill_dispatch``, ``.decode_wait``, ``.decode_apply``,
  ``.prefill_wait``, ``.prefill_apply``, ``.publish``; a phase that had
  nothing to do leaves no span; ``.decode_wait`` / ``.decode_apply`` read
  the step the call before launched), and per request ``request.queued`` /
  ``request.prefill`` / ``request.decode`` sharing ``request_id``
  (docs/observability.md lists the attrs).

Greedy decode through the engine is token-identical to the dense
`generate` (tested across block sizes) — continuous batching changes
WHEN a request computes, never WHAT it computes.  The look-ahead changes
when the host HAS a token (a call later), never which: a finish by
length is counted ahead and nothing runs past it; a stop token or a
cancel is seen with one step in flight, whose token for that slot is
dropped.
"""

from __future__ import annotations

import math
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp

from tpu_dist.observe import events as ev_mod
from tpu_dist.observe import compile_spans, spans
from tpu_dist.observe.registry import REGISTRY
from tpu_dist.serve.paged_kv import BlockAllocator
from tpu_dist.serve.sampling import sample_slots, slot_keys


@dataclass
class SamplingParams:
    """Per-request sampling config (the runtime analog of `generate`'s
    static kwargs).  ``temperature=0`` is greedy; ``seed`` keys the
    request's private PRNG stream."""

    temperature: float = 0.0
    top_k: int | None = None
    top_p: float | None = None
    seed: int = 0


@dataclass
class ServeConfig:
    """Engine sizing.  ``max_seq`` caps prompt + output per request (it
    must fit the model's ``max_seq``); the pool holds ``num_blocks``
    blocks of ``block_size`` positions each, shared by all slots;
    ``prefill_chunk`` is the prompt-ingestion quantum (one chunk per
    engine step, interleaved with decode)."""

    max_batch: int = 8
    block_size: int = 16
    num_blocks: int = 128
    max_seq: int = 256
    prefill_chunk: int = 32
    prefill_batch: int = 4
    decode_event_every: int = 8
    cache_dtype: object = None
    # HBM budget for the admission memory check (bytes).  None = read
    # the live device limit (`observe.memory.memory_snapshot`; absent
    # on CPU-sim).  A grant that would push weights + granted KV blocks
    # past this emits a `warning` event — tests inject a fake limit.
    bytes_limit: int | None = None


@dataclass
class Request:
    """Internal request record (front-ends construct via
    `ServeEngine.submit`)."""

    request_id: int
    prompt: np.ndarray
    max_new_tokens: int
    sampling: SamplingParams
    stop_token: int | None = None
    # runtime state
    state: str = "queued"  # queued | prefill | decode | finished
    slot: int = -1
    blocks: list = field(default_factory=list)
    prefill_pos: int = 0
    tokens: list = field(default_factory=list)
    arrival_time: float = 0.0
    admit_time: float | None = None
    first_token_time: float | None = None
    finish_time: float | None = None
    token_times: list = field(default_factory=list)
    finish_reason: str | None = None


@dataclass
class RequestResult:
    """What the front-end hands back: the emitted tokens plus the
    latency observables the serving benches report."""

    request_id: int
    tokens: np.ndarray
    finish_reason: str
    prompt_len: int
    arrival_time: float
    first_token_time: float | None
    finish_time: float
    token_times: list
    admit_time: float | None = None  # None: cancelled while queued

    @property
    def emitted(self) -> int:
        return int(self.tokens.size)

    @property
    def ttft(self) -> float | None:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    @property
    def tpot_mean(self) -> float | None:
        """Mean time per output token after the first (None for
        single-token or unstarted requests)."""
        if self.first_token_time is None or self.emitted < 2:
            return None
        return (self.finish_time - self.first_token_time) / (self.emitted - 1)


class ServeEngine:
    """The continuous-batching step loop over one model + paged pool.

    ``now``: injectable clock (tests pass a fake for deterministic
    latency fields).  The default is `observe.spans`' clock, so the
    ``request.*`` spans, stamped with it, lie on the phase spans' axis.
    The engine is single-threaded by design — callers drive `step()` (or
    `run_until_drained()`); thread-safety belongs to the front-end.
    """

    def __init__(self, lm, params, config: ServeConfig | None = None, *,
                 now=time.perf_counter, events=None):
        compile_spans.install()
        # what building an engine costs the host, kept past the ring's wrap
        with spans.span("engine.init", keep=True):
            self._init(lm, params, config, now, events)

    def _init(self, lm, params, config, now, events) -> None:
        cfg = config or ServeConfig()
        if cfg.max_seq > lm.max_seq:
            raise ValueError(
                f"config max_seq {cfg.max_seq} exceeds model max_seq "
                f"{lm.max_seq}"
            )
        if cfg.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {cfg.prefill_chunk}"
            )
        if cfg.prefill_batch < 1:
            raise ValueError(
                f"prefill_batch must be >= 1, got {cfg.prefill_batch}"
            )
        self.lm, self.params, self.cfg = lm, params, cfg
        self._now = now
        self.events = events if events is not None else ev_mod.from_env()
        # under TPU_DIST_TELEMETRY: the Chrome-trace file of the ring, this
        # engine's spans in it, written at exit and on the crash paths
        spans.from_env()
        from tpu_dist.observe import flightrec as _flightrec_mod
        from tpu_dist.observe import memory as _memory_mod

        self._flight = _flightrec_mod.get()
        self._memory = _memory_mod.WatermarkSampler(flight=self._flight)
        self.blocks_per_seq = math.ceil(cfg.max_seq / cfg.block_size)
        self.context_len = self.blocks_per_seq * cfg.block_size
        self.allocator = BlockAllocator(cfg.num_blocks)
        dtype = cfg.cache_dtype or params["embed"]["table"].dtype
        # the model's own: paged pools under "kv", and under "state"
        # whatever it keeps per decode slot (a recurrent state)
        from tpu_dist.parallel import per_device_bytes

        with spans.span("engine.init_cache", keep=True) as sp:
            self.cache = lm.init_serve_cache(
                cfg.max_batch, cfg.num_blocks, cfg.block_size, dtype
            )
            self.kv_pool_bytes = int(per_device_bytes(self.cache["kv"]))
            self.state_bytes = int(per_device_bytes(self.cache["state"]))
            sp.attrs.update(kv_bytes=self.kv_pool_bytes, state_bytes=self.state_bytes)
        self.scratch = cfg.num_blocks

        S, MB = cfg.max_batch, self.blocks_per_seq
        self.block_tables = np.full((S, MB), self.scratch, np.int32)
        self.index = np.zeros((S,), np.int32)
        self.active = np.zeros((S,), bool)
        self.last_tok = np.zeros((S,), np.int32)
        self.temperature = np.zeros((S,), np.float32)
        self.top_k = np.zeros((S,), np.int32)
        self.top_p = np.ones((S,), np.float32)
        self.seeds = np.zeros((S,), np.int32)
        self.counters = np.zeros((S,), np.int32)

        self.slots: list[Request | None] = [None] * S
        self.queue: deque[Request] = deque()
        self._prefillq: deque[int] = deque()
        self._cancelled: set[int] = set()
        self.results: dict[int, RequestResult] = {}
        self.step_count = 0
        self.steps_with_decode = 0
        self.steps_with_prefill = 0
        self._next_id = 0
        # (kind, ...) tuples, appended in processing order — the
        # determinism tests' observable.  Two a request: bounded, so a
        # server's memory does not grow with the requests it has served.
        self.audit: deque[tuple] = deque(maxlen=65536)

        self._decode_fn = self._build_decode_fn(greedy=False)
        self._decode_fn_greedy = self._build_decode_fn(greedy=True)
        self._rows_fn = self._build_rows_fn()
        self._prefill_fn = self._build_prefill_fn()
        # tokens of each slot's request not yet launched: the host counts
        # what it has dispatched, so a finish by length is known a step
        # before its token arrives and the slot is not fed past it
        self._unfed = np.zeros((S,), np.int32)
        # device-resident decode state: the per-slot scheduling arrays
        # ride the jitted step's output back into the next step's input
        # as ONE packed int32 array (block tables, active mask, sampling
        # ints, last token, position, token counter) plus one small f32
        # array (temperature, top_p) — a steady-state decode step
        # transfers nothing host->device.  A slot that joins, leaves or is
        # granted blocks marks its row `_stale`; the next dispatch writes
        # those rows over the device's (`serve_slot_rows`) and leaves the
        # others what the device carried forward, which the host's mirrors
        # (one readback behind) could not give back
        self._dint, self._dflt = jax.device_put(self._pack_state())
        self._stale = np.zeros((S,), bool)
        # the decode step launched by the last call and not read yet:
        # (tokens' device handle, [(slot, request) it fed])
        self._unread = None
        # why the next decode launch finds the chip idle, None while it
        # is fed: the last call launched no decode step (`drained`,
        # `prefill_priority`) or waited for a prefill round's first tokens
        # behind the one it launched (`prefill_join`)
        self._gap = "drained"
        self._warming = False
        self._g_occ = REGISTRY.gauge(
            "tpu_dist_serve_batch_occupancy",
            "active decode slots in the serving batch",
        )
        self._g_queue = REGISTRY.gauge(
            "tpu_dist_serve_queue_depth", "requests waiting for admission"
        )
        self._g_blocks = REGISTRY.gauge(
            "tpu_dist_serve_kv_blocks_used", "allocated KV pool blocks"
        )
        self._g_util = REGISTRY.gauge(
            "tpu_dist_serve_kv_block_utilization",
            "allocated fraction of the KV block pool",
        )
        self._h_ttft = REGISTRY.histogram(
            "tpu_dist_serve_ttft_seconds", "time to first token"
        )
        self._h_tpot = REGISTRY.histogram(
            "tpu_dist_serve_tpot_seconds", "per-token decode latency"
        )
        counter = lambda name, what: REGISTRY.counter(  # noqa: E731
            f"tpu_dist_serve_{name}_total", what
        )
        self._c_admitted = counter("admitted", "requests admitted to a slot")
        self._c_blocked = counter(
            "blocked_steps",
            "engine steps that left the queue's head waiting, by cause",
        )
        self._c_prefill_rows = counter(
            "prefill_rows", "request chunks run by prefill rounds"
        )
        self._c_prefill_real = counter(
            "prefill_real_tokens", "prompt tokens run by prefill rounds"
        )
        self._c_prefill_padded = counter(
            "prefill_padded_tokens",
            "token places of prefill rounds (rows x chunk), padding included",
        )
        self._c_repacks = counter(
            "state_repacks",
            "decode dispatches that wrote slot rows over the packed state",
        )
        self._c_decode_steps = counter("decode_steps", "decode steps dispatched")
        self._c_ahead = counter(
            "decode_ahead",
            "decode steps dispatched while the step before was still unread",
        )
        self._c_gaps = counter(
            "decode_gaps",
            "decode steps dispatched onto a chip the host had let run dry, "
            "by cause",
        )
        # what the model's serving programs count themselves (routed
        # picks, tokens an expert): running totals that ride the decode
        # step's readback, position by position as `lm.serve_counters` says
        self._model_counters = [
            (name, counter(name, f"{name}, counted by the model's programs"),
             key, values)
            for name, key, values in lm.serve_counters
        ]
        self._model_counted = None
        # Memory breakdown: what this engine keeps resident — weights
        # vs KV pool (allocated in full at init; blocks are GRANTS of
        # that pool) vs whatever headroom the device has left for
        # activations.  `bytes_limit` comes from the config (tests/
        # operators) or the live device limit (None on CPU-sim).
        self.weights_bytes = int(per_device_bytes(self.params))
        # the pool holds num_blocks grantable blocks + 1 scratch block
        self.kv_block_bytes = self.kv_pool_bytes // (cfg.num_blocks + 1)
        self.bytes_limit = (
            cfg.bytes_limit
            if cfg.bytes_limit is not None
            else self._memory.snapshot().get("bytes_limit")
        )
        REGISTRY.gauge(
            "tpu_dist_serve_weights_bytes", "model weight bytes resident"
        ).set(self.weights_bytes)
        REGISTRY.gauge(
            "tpu_dist_serve_kv_pool_bytes",
            "paged KV pool bytes resident (allocated at init)",
        ).set(self.kv_pool_bytes)
        REGISTRY.gauge(
            "tpu_dist_serve_state_bytes",
            "per-slot recurrent state bytes resident (allocated at init)",
        ).set(self.state_bytes)

    # ------------------------------------------------------------- jit fns

    # packed int-state column layout (after the MB block-table columns)
    _ACTIVE, _TOPK, _SEED, _LASTTOK, _INDEX, _COUNTER = range(6)

    def _pack_state(self):
        """The host's mirrors as the packed state.  A row is the device's
        truth only while it is stale (just joined, left or granted); a
        row that goes on decoding is a readback behind the device's."""
        MB = self.blocks_per_seq
        ints = np.empty((self.cfg.max_batch, MB + 6), np.int32)
        ints[:, :MB] = self.block_tables
        ints[:, MB + self._ACTIVE] = self.active & (self._unfed > 0)
        ints[:, MB + self._TOPK] = self.top_k
        ints[:, MB + self._SEED] = self.seeds
        ints[:, MB + self._LASTTOK] = self.last_tok
        ints[:, MB + self._INDEX] = self.index
        ints[:, MB + self._COUNTER] = self.counters
        flt = np.stack([self.temperature, self.top_p], axis=1)
        return ints, flt.astype(np.float32)

    def _build_decode_fn(self, *, greedy: bool):
        """One batched decode step over the packed state.
        ``greedy=True`` is the fast path taken when every active slot
        has temperature 0 — no sorts, no key derivation, plain argmax
        (exactly `generate`'s greedy op)."""
        lm, bs, MB = self.lm, self.cfg.block_size, self.blocks_per_seq

        def fn(params, cache, ints, flt):
            block_tables = ints[:, :MB]
            active = ints[:, MB + self._ACTIVE].astype(bool)
            last_tok = ints[:, MB + self._LASTTOK]
            index = ints[:, MB + self._INDEX]
            # row i is slot i: no slot indices
            logits, cache, counted = lm.apply_paged(
                params, last_tok[:, None], cache, block_tables,
                index[:, None], active[:, None], None, bs,
            )
            with jax.named_scope("sample"):
                if greedy:
                    toks = jnp.argmax(logits[:, 0], axis=-1).astype(
                        last_tok.dtype
                    )
                else:
                    keys = slot_keys(
                        ints[:, MB + self._SEED],
                        ints[:, MB + self._COUNTER],
                    )
                    toks = sample_slots(
                        logits[:, 0], keys, flt[:, 0],
                        ints[:, MB + self._TOPK], flt[:, 1],
                        last_tok.dtype,
                    )
            with jax.named_scope("state_update"):
                inc = active.astype(jnp.int32)
                ints = ints.at[:, MB + self._LASTTOK].set(
                    jnp.where(active, toks, last_tok)
                )
                ints = ints.at[:, MB + self._INDEX].add(inc)
                ints = ints.at[:, MB + self._COUNTER].add(inc)
            if counted is not None:  # one readback carries both
                toks = jnp.concatenate([toks, counted.astype(toks.dtype)])
            return toks, ints, cache

        # the program's name on the trace's `XLA Modules` line
        fn.__name__ = "serve_decode_greedy" if greedy else "serve_decode_sampled"
        return jax.jit(fn, donate_argnums=(1, 2))

    def _build_rows_fn(self):
        """The rows of ``rows`` (a mask over the slots) written over the
        device's packed state, every other row kept as the decode steps
        carried it: ahead of a decode dispatch whose slot map changed,
        behind the step still in flight on the same donated chain."""

        def serve_slot_rows(ints, flt, rows, new_ints, new_flt):
            with jax.named_scope("state_update"):
                rows = rows[:, None]
                return (
                    jnp.where(rows, new_ints, ints),
                    jnp.where(rows, new_flt, flt),
                )

        return jax.jit(serve_slot_rows, donate_argnums=(0, 1))

    def _build_prefill_fn(self):
        """One prompt chunk for EACH of P pending requests (P = however
        many rows the host passes, retraced per distinct P up to
        ``prefill_batch``) — distinct requests only, since a request's
        later chunks attend its earlier ones.  Also samples each row's
        would-be first output token from its last real position (the
        host uses it only for rows whose prompt just completed)."""
        lm, bs, C = self.lm, self.cfg.block_size, self.cfg.prefill_chunk
        MB = self.blocks_per_seq

        def serve_prefill(params, cache, ints, flt):
            # ints columns: [tokens(C) | block_table(MB) | start |
            #                real_len | top_k | seed | slot]
            tokens = ints[:, :C]
            block_tables = ints[:, C : C + MB]
            start = ints[:, C + MB]
            real_len = ints[:, C + MB + 1]
            positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)
            write_mask = jnp.arange(C)[None, :] < real_len[:, None]
            logits, cache, _ = lm.apply_paged(
                params, tokens, cache, block_tables, positions,
                write_mask, ints[:, C + MB + 4], bs,
            )
            with jax.named_scope("sample"):
                last = jnp.take_along_axis(
                    logits,
                    jnp.maximum(real_len, 1)[:, None, None] - 1,
                    axis=1,
                )[:, 0]
                keys = slot_keys(
                    ints[:, C + MB + 3], jnp.zeros_like(real_len)
                )
                toks = sample_slots(
                    last, keys, flt[:, 0], ints[:, C + MB + 2], flt[:, 1],
                    tokens.dtype,
                )
            return toks, cache

        return jax.jit(serve_prefill, donate_argnums=(1,))

    # ------------------------------------------------------ static analysis

    def analysis_programs(self) -> dict:
        """The engine's hot compiled programs, exposed for
        `tpu_dist.analysis`: ``{name: (jitted_fn, example_args)}`` with
        `jax.ShapeDtypeStruct` arguments — lowering them compiles the
        REAL serving step (same shapes, same donation) without touching
        (or donating) any live buffer.

        ``serve_decode`` is the steady-state sampled decode step (the
        per-token hot path; cache + packed state donated);
        ``serve_prefill`` is one full-width chunked-prefill round."""
        sds = lambda t: jax.tree.map(  # noqa: E731
            lambda x: jax.ShapeDtypeStruct(
                tuple(np.shape(x)), np.asarray(x).dtype
                if not hasattr(x, "dtype") else x.dtype
            ),
            t,
        )
        params, cache = sds(self.params), sds(self.cache)
        ints, flt = self._pack_state()
        C, MB, Pb = (
            self.cfg.prefill_chunk, self.blocks_per_seq,
            self.cfg.prefill_batch,
        )
        p_ints = jax.ShapeDtypeStruct((Pb, C + MB + 5), np.int32)
        p_flt = jax.ShapeDtypeStruct((Pb, 2), np.float32)
        return {
            "serve_decode": (
                self._decode_fn, (params, cache, sds(ints), sds(flt))
            ),
            "serve_prefill": (
                self._prefill_fn, (params, cache, p_ints, p_flt)
            ),
        }

    # ------------------------------------------------------------- memory

    def memory_breakdown(self) -> dict:
        """The serve-side resident story: weights vs KV pool (split
        into granted and free blocks) vs the per-slot recurrent state
        (whole at init, like the pool) vs activation headroom against
        ``bytes_limit`` (None when no limit is known — CPU-sim without
        a configured budget).  The `observe.memory` snapshot rides
        along so plan (this breakdown) and live (HBM/RSS) are one
        record."""
        granted = self.allocator.used * self.kv_block_bytes
        headroom = (
            int(self.bytes_limit) - self.weights_bytes - self.kv_pool_bytes
            - self.state_bytes
            if self.bytes_limit is not None else None
        )
        return {
            "weights_bytes": self.weights_bytes,
            "kv_pool_bytes": self.kv_pool_bytes,
            "kv_granted_bytes": int(granted),
            "kv_block_bytes": self.kv_block_bytes,
            "state_bytes": self.state_bytes,
            "bytes_limit": self.bytes_limit,
            "activation_headroom_bytes": headroom,
            "live": self._memory.snapshot(),
        }

    def _resident_rows(self) -> list[dict]:
        return [
            {"class": "weights", "bytes": self.weights_bytes},
            {"class": "kv_pool", "bytes": self.kv_pool_bytes},
            {"class": "state", "bytes": self.state_bytes},
        ]

    def _check_block_grant(self, req: Request, need: int) -> None:
        """Admission memory check: warn (once per request) when this
        grant pushes weights + per-slot state + granted KV blocks past
        ``bytes_limit``
        — the pool itself is preallocated, so the grant cannot OOM by
        itself, but a plan whose grants exceed the budget means the
        pool was sized past the device and the NEXT activation spike
        will be the thing that dies.  Called AFTER ``alloc(need)``, so
        ``allocator.used`` already includes this grant; admission runs
        once per request, so no dedup is needed."""
        if self.bytes_limit is None:
            return
        projected = (
            self.weights_bytes + self.state_bytes
            + self.allocator.used * self.kv_block_bytes
        )
        if projected <= self.bytes_limit:
            return
        self._flight.record(
            "memory", phase="admit", projected_bytes=int(projected),
            bytes_limit=int(self.bytes_limit),
        )
        self.events.emit(
            "warning",
            reason="kv_grant_over_limit",
            request_id=req.request_id,
            blocks=need,
            projected_bytes=int(projected),
            bytes_limit=int(self.bytes_limit),
            over_bytes=int(projected - self.bytes_limit),
        )

    def _oom(self, exc: BaseException, phase: str) -> None:
        """RESOURCE_EXHAUSTED on a serving step path: plan-vs-live OOM
        forensics through the flight recorder (`observe.memory`)."""
        from tpu_dist.observe import memory as _memory_mod

        if not _memory_mod.is_resource_exhausted(exc):
            return
        _memory_mod.record_oom(
            exc,
            phase=phase,
            sampler=self._memory,
            resident=self._resident_rows(),
            plan=self.memory_breakdown(),
            events_logger=self.events,
        )

    # ---------------------------------------------------------- front door

    def submit(self, prompt, max_new_tokens: int, *,
               sampling: SamplingParams | None = None,
               stop_token: int | None = None) -> int:
        """Queue one request; returns its id.  Admission happens inside
        `step()` (a submit never blocks on pool space)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        if prompt.size + max_new_tokens > self.cfg.max_seq:
            raise ValueError(
                f"prompt {prompt.size} + max_new_tokens {max_new_tokens} "
                f"exceeds serve max_seq {self.cfg.max_seq}"
            )
        need = math.ceil(
            (prompt.size + max_new_tokens) / self.cfg.block_size
        )
        if need > self.cfg.num_blocks:
            # admitting is impossible even with an empty pool; queueing
            # it would livelock the FIFO head forever
            raise ValueError(
                f"request needs {need} KV blocks but the pool holds "
                f"only {self.cfg.num_blocks}"
            )
        rid = self._next_id
        self._next_id += 1
        req = Request(
            request_id=rid, prompt=prompt, max_new_tokens=max_new_tokens,
            sampling=sampling or SamplingParams(), stop_token=stop_token,
            arrival_time=self._now(),
        )
        self.queue.append(req)
        return rid

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or in-flight request.  Queued: removed
        immediately.  Running: evicted at the start of the next step
        (the tokens the host has are returned with ``finish_reason
        'cancelled'``; the token of a decode step still in flight is
        dropped).  Returns False for unknown/finished ids."""
        for i, req in enumerate(self.queue):
            if req.request_id == request_id:
                del self.queue[i]
                self._finalize(req, "cancelled", self._now())
                return True
        for req in self.slots:
            if req is not None and req.request_id == request_id:
                self._cancelled.add(request_id)
                return True
        return False

    @property
    def pending(self) -> bool:
        """True until the last token of the last request has been applied
        and no decode step is left unread."""
        return (
            bool(self.queue)
            or any(r is not None for r in self.slots)
            or self._unread is not None
        )

    def run_until_drained(self, max_steps: int = 100_000):
        """Drive `step()` until queue and slots are empty; returns the
        results dict (id -> `RequestResult`)."""
        steps = 0
        while self.pending:
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(
                    f"engine not drained after {max_steps} steps "
                    f"(queue={len(self.queue)}, "
                    f"occupied={sum(r is not None for r in self.slots)})"
                )
        return self.results

    # ------------------------------------------------------------ the step

    def step(self) -> None:
        """One engine step: evict cancels, admit, LAUNCH decode step n
        and one batched prefill round, then READ decode step n-1 (the
        one the call before launched) and the round, then publish
        telemetry.  The chip runs step n while the host keeps step
        n-1's books, returns to its caller and comes back to launch
        n+1: between two decode programs it does not wait for the host.

        What the host shows (``active``, ``index``, ``Request.tokens``,
        the events, the histograms) is therefore one step behind the
        device; a token counts, and is timed, when the host has it.  A
        finish by length is counted ahead (`_unfed`): the slot is not
        fed past its last token.  A stop token or a cancel is seen with
        one step in flight; that step's token for the slot is dropped.
        Its write lands inside the request's own grant or its slot's own
        state, and whatever touches those next is dispatched behind it on
        the one donated cache chain.  The first token of a prefill round
        is read in the call that launched the round, as before.  A call
        with nothing to launch reads what is unread: the pipeline empties
        itself.

        Prefill-priority at low occupancy: while more prefills would
        remain after this round and no more than half the decode slots
        are active, the decode step is skipped for this engine step —
        filling slots fast raises the occupancy every later decode step
        amortizes over, at the bounded cost of delaying at most half a
        batch by one prefill round."""
        with spans.span("engine.step", step=self.step_count) as sp:
            with spans.span("engine.admit"):
                self._process_cancels()
                admitted, blocked = self._admit()
            prefer_prefill = (
                len(self._prefillq) > self.cfg.prefill_batch
                and self.occupancy() <= self.cfg.max_batch // 2
            )
            unread = self._unread
            try:
                self._unread = self._decode_dispatch(
                    ahead=unread is not None, skip=prefer_prefill
                )
            except Exception as e:
                self._oom(e, "decode")
                raise
            try:
                prefill_ctx = self._prefill_dispatch()
            except Exception as e:
                self._oom(e, "prefill")
                raise
            try:
                did_decode = self._decode_complete(unread)
            except Exception as e:
                self._oom(e, "decode")
                raise
            try:
                did_prefill = self._prefill_complete(prefill_ctx)
            except Exception as e:
                self._oom(e, "prefill")
                raise
            with spans.span("engine.publish"):
                if self.events.enabled and not self._warming:
                    if did_decode:
                        self._memory.sample("decode")
                    if did_prefill:
                        self._memory.sample("prefill")
                self.steps_with_prefill += bool(did_prefill)
                self.steps_with_decode += bool(did_decode)
                if did_prefill or did_decode:
                    # Flight ring (observe.flightrec): one deque append
                    # per working step, so a wedged decode gang's
                    # post-mortem dump shows the serving loop's last
                    # completed steps too.
                    self._flight.record(
                        "step", step=self.step_count, phase="readback",
                        occupancy=self.occupancy(),
                    )
                occ = self._publish(did_prefill or did_decode)
            sp.attrs.update(
                occupancy=occ, queued=len(self.queue),
                admitted=admitted, blocked=blocked,
            )
        self.step_count += 1

    def _process_cancels(self) -> None:
        if not self._cancelled:
            return
        tnow = self._now()
        for s, req in enumerate(self.slots):
            if req is not None and req.request_id in self._cancelled:
                self._cancelled.discard(req.request_id)
                if s in self._prefillq:
                    self._prefillq.remove(s)
                self._evict(s, "cancelled", tnow)
        self._cancelled.clear()  # ids that were already finished

    def _count(self, counter, amount: int = 1, **labels) -> None:
        """The registry's totals leave warm-up's throwaway requests out,
        as its histograms do."""
        if not self._warming:
            counter.inc(amount, **labels)

    def _admit(self) -> tuple[int, str]:
        """Admit from the queue's head while a slot and its blocks are
        free.  Returns how many were admitted and why the head stayed:
        ``""`` (the queue emptied), ``"slots"`` or ``"blocks"``."""
        admitted, blocked = 0, ""
        while self.queue:
            free = [s for s, r in enumerate(self.slots) if r is None]
            if not free:
                blocked = "slots"
                break
            req = self.queue[0]
            need = math.ceil(
                (req.prompt.size + req.max_new_tokens) / self.cfg.block_size
            )
            blocks = self.allocator.alloc(need)
            if blocks is None:
                # head-of-line blocks; FIFO stays deterministic
                blocked = "blocks"
                break
            self._check_block_grant(req, need)
            self.queue.popleft()
            admitted += 1
            req.admit_time = self._now()
            spans.record(
                "request.queued", req.arrival_time, req.admit_time,
                request_id=req.request_id,
            )
            s = free[0]
            req.slot, req.blocks, req.state = s, blocks, "prefill"
            self.slots[s] = req
            self.block_tables[s, :] = self.scratch
            self.block_tables[s, : len(blocks)] = blocks
            self.index[s] = 0
            self.active[s] = False
            sp = req.sampling
            self.temperature[s] = sp.temperature
            self.top_k[s] = 0 if sp.top_k is None else sp.top_k
            self.top_p[s] = 1.0 if sp.top_p is None else sp.top_p
            # seed rides the packed int32 state: keep the low 32 bits
            # (two's complement) so any Python int is a valid seed
            s32 = sp.seed & 0xFFFFFFFF
            self.seeds[s] = s32 - (1 << 32) if s32 >= 1 << 31 else s32
            self.counters[s] = 0
            self._stale[s] = True
            self._prefillq.append(s)
            self.audit.append(
                ("admit", req.request_id, s, tuple(blocks), self.step_count)
            )
            self.events.emit(
                "request_admit",
                request_id=req.request_id,
                prompt_tokens=int(req.prompt.size),
                max_new_tokens=int(req.max_new_tokens),
                queue_depth=len(self.queue),
            )
        if admitted:
            self._count(self._c_admitted, admitted)
        if blocked:
            self._count(self._c_blocked, cause=blocked)
        return admitted, blocked

    def _prefill_dispatch(self):
        """Assemble + dispatch one chunk for each of (up to
        ``prefill_batch``) oldest prefilling requests in ONE batched
        call — distinct requests only, since a request's later chunks
        attend its earlier ones.  Returns the (chunks, first-token
        device handle) context for `_prefill_complete`, or None."""
        if not self._prefillq:
            return None
        C, MB = self.cfg.prefill_chunk, self.blocks_per_seq
        take = list(self._prefillq)[: self.cfg.prefill_batch]
        P = len(take)
        with spans.span("engine.prefill_dispatch", rows=P, chunk=C) as sp:
            ints = np.zeros((P, C + MB + 5), np.int32)
            flt = np.zeros((P, 2), np.float32)
            chunks = []
            for r, s in enumerate(take):
                req = self.slots[s]
                start = req.prefill_pos
                chunk = req.prompt[start : start + C]
                chunks.append((s, req, start, chunk.size))
                ints[r, : chunk.size] = chunk
                ints[r, C : C + MB] = self.block_tables[s]
                ints[r, C + MB] = start
                ints[r, C + MB + 1] = chunk.size
                ints[r, C + MB + 2] = self.top_k[s]
                ints[r, C + MB + 3] = self.seeds[s]
                ints[r, C + MB + 4] = s
                flt[r, 0] = self.temperature[s]
                flt[r, 1] = self.top_p[s]
            first_toks, self.cache = self._prefill_fn(
                self.params, self.cache, ints, flt
            )
            real = sum(size for _, _, _, size in chunks)
            sp.attrs["real_tokens"] = real
        self._count(self._c_prefill_rows, P)
        self._count(self._c_prefill_real, real)
        self._count(self._c_prefill_padded, P * C)
        return chunks, first_toks

    def _prefill_complete(self, ctx) -> bool:
        """Apply a dispatched prefill round: advance positions; rows
        whose prompt completed get their first output token (sampled
        from the chunk's last logits exactly as `generate` samples from
        its prefill logits — this is the TTFT moment) and join the
        decode batch."""
        if ctx is None:
            return False
        chunks, first_toks = ctx
        finishing = [
            r for r, (s, req, start, size) in enumerate(chunks)
            if start + size >= req.prompt.size
        ]
        toks_np = None
        if finishing:
            # the one place the host waits for the prefill round, and
            # with it for the decode step launched ahead of the round
            with spans.span("engine.prefill_wait"):
                toks_np = np.asarray(first_toks)
            self._gap = self._gap or "prefill_join"
        tnow = self._now()
        with spans.span("engine.prefill_apply"):
            for r, (s, req, start, size) in enumerate(chunks):
                req.prefill_pos += size
                self.events.emit(
                    "prefill",
                    request_id=req.request_id,
                    chunk=start // self.cfg.prefill_chunk,
                    tokens=size,
                    done=req.prefill_pos >= req.prompt.size,
                )
                if req.prefill_pos < req.prompt.size:
                    continue
                self._prefillq.remove(s)
                tok = int(toks_np[r])
                req.tokens.append(tok)
                req.token_times.append(tnow)
                req.first_token_time = tnow
                spans.record(
                    "request.prefill", req.admit_time, tnow,
                    request_id=req.request_id,
                    # the slot's recurrent state began from zero
                    state_reset=self.state_bytes > 0,
                )
                if not self._warming:
                    self._h_ttft.observe(tnow - req.arrival_time)
                self.counters[s] += 1
                self.last_tok[s] = tok
                self.index[s] = req.prompt.size
                req.state = "decode"
                self.active[s] = True
                self._unfed[s] = req.max_new_tokens - 1
                self._stale[s] = True
                if self._finished_by(req, tok):
                    self._evict(s, self._finish_reason(req, tok), tnow)
        return True

    def _decode_dispatch(self, *, ahead: bool, skip: bool):
        """Launch one batched token for every slot with a token still to
        be fed, from the state the device carried forward; ``ahead``: the
        step before is still unread; ``skip``: prefill has priority this
        step.  Returns what `_decode_complete` reads a call later, (the
        tokens' device handle, the (slot, request) pairs fed), or None."""
        feed = self.active & (self._unfed > 0)
        fed = np.nonzero(feed)[0]
        if skip or not fed.size:
            self._gap = "prefill_priority" if fed.size else "drained"
            return None
        repacked = bool(self._stale.any())
        attrs = {"repacked": repacked, "ahead": ahead, "fed": int(fed.size)}
        gap, self._gap = self._gap, None
        if gap:
            attrs["gap"] = gap
        with spans.span("engine.decode_dispatch", **attrs):
            if repacked:
                ints, flt = self._pack_state()
                self._dint, self._dflt = self._rows_fn(
                    self._dint, self._dflt, self._stale, ints, flt
                )
                self._stale = np.zeros_like(self._stale)
            fn = (
                self._decode_fn_greedy
                if not self.temperature[feed].any()
                else self._decode_fn
            )
            toks, self._dint, self.cache = fn(
                self.params, self.cache, self._dint, self._dflt
            )
        self._unfed[fed] -= 1
        # fed its last: off the device's batch before the next launch
        self._stale[fed[self._unfed[fed] == 0]] = True
        self._count(self._c_decode_steps)
        if repacked:
            self._count(self._c_repacks)
        if ahead:
            self._count(self._c_ahead)
        if gap:
            self._count(self._c_gaps, cause=gap)
        return toks, [(int(s), self.slots[s]) for s in fed]

    def _decode_complete(self, unread) -> bool:
        """Read back the decode step the call before launched, then
        finish/evict the streams that completed — THE every-step
        admit/evict cycle's compute half."""
        if unread is None:
            return False
        toks, fed = unread
        with spans.span("engine.decode_wait"):
            toks_np = np.asarray(toks)  # host sync: the step boundary
        tnow = self._now()
        with spans.span("engine.decode_apply") as sp:
            if self._model_counters:
                self._count_model(toks_np[self.cfg.max_batch:], sp)
            # a request that stopped or was cancelled while this step was
            # in flight: its token here is the overrun, never seen
            fed = [(s, req) for s, req in fed if req.state != "finished"]
            live = [s for s, _ in fed]
            self.last_tok[live] = toks_np[live]
            self.index[live] += 1
            self.counters[live] += 1
            for s, req in fed:
                tok = int(toks_np[s])
                if req.token_times and not self._warming:
                    self._h_tpot.observe(tnow - req.token_times[-1])
                req.tokens.append(tok)
                req.token_times.append(tnow)
                if self._finished_by(req, tok):
                    self._evict(s, self._finish_reason(req, tok), tnow)
        return True

    def _count_model(self, totals: np.ndarray, sp) -> None:
        """The model's running int32 totals since the last readback, into
        the registry and onto the ``engine.decode_apply`` span (a count of
        several labels as a tuple).  Prefill rounds count into the same
        totals, so theirs arrive with the next decode step's."""
        if self._model_counted is None:
            self._model_counted = np.zeros_like(totals)
        # int32 wraps; the difference of two wrapped totals does not
        delta = (totals - self._model_counted).astype(np.uint32)
        self._model_counted = totals
        at = 0
        for name, counter, key, values in self._model_counters:
            if key is None:
                sp.attrs[name] = int(delta[at])
                self._count(counter, sp.attrs[name])
                at += 1
                continue
            part = delta[at : at + len(values)]
            sp.attrs[name] = tuple(int(d) for d in part)
            for value, d in zip(values, sp.attrs[name]):
                self._count(counter, d, **{key: str(value)})
            at += len(values)

    @staticmethod
    def _finished_by(req: Request, tok: int) -> bool:
        return (
            len(req.tokens) >= req.max_new_tokens
            or (req.stop_token is not None and tok == req.stop_token)
        )

    @staticmethod
    def _finish_reason(req: Request, tok: int) -> str:
        if req.stop_token is not None and tok == req.stop_token:
            return "stop"
        return "length"

    def _evict(self, s: int, reason: str, tnow: float) -> None:
        req = self.slots[s]
        self.allocator.free(req.blocks)
        req.blocks = []
        self.slots[s] = None
        self.block_tables[s, :] = self.scratch
        self.active[s] = False
        self._unfed[s] = 0
        self._stale[s] = True
        self._finalize(req, reason, tnow)

    def _finalize(self, req: Request, reason: str, tnow: float) -> None:
        req.state, req.finish_reason, req.finish_time = (
            "finished", reason, tnow,
        )
        result = RequestResult(
            request_id=req.request_id,
            tokens=np.asarray(req.tokens, np.int32),
            finish_reason=reason,
            prompt_len=int(req.prompt.size),
            arrival_time=req.arrival_time,
            first_token_time=req.first_token_time,
            finish_time=tnow,
            token_times=list(req.token_times),
            admit_time=req.admit_time,
        )
        self.results[req.request_id] = result
        if req.first_token_time is not None:
            spans.record(
                "request.decode", req.first_token_time, tnow,
                request_id=req.request_id, emitted=len(req.tokens),
                finish_reason=reason,
            )
        self.audit.append(
            ("finish", req.request_id, reason, len(req.tokens),
             self.step_count)
        )
        self.events.emit(
            "request_finish",
            request_id=req.request_id,
            emitted=len(req.tokens),
            finish_reason=reason,
            ttft=result.ttft,
            tpot_mean=result.tpot_mean,
        )

    def _publish(self, worked: bool) -> int:
        """Gauges and the sampled ``decode_step`` event; returns the
        occupancy it published."""
        occ = self.occupancy()
        self._g_occ.set(occ)
        self._g_queue.set(len(self.queue))
        self._g_blocks.set(self.allocator.used)
        self._g_util.set(self.allocator.utilization())
        if worked and self.step_count % self.cfg.decode_event_every == 0:
            self.events.emit(
                "decode_step",
                step=self.step_count,
                occupancy=occ,
                queue_depth=len(self.queue),
                kv_blocks_used=self.allocator.used,
                kv_block_utilization=self.allocator.utilization(),
            )
        return occ

    # ----------------------------------------------------------- accessors

    def occupancy(self) -> int:
        return int(self.active.sum())

    def warmup(self) -> None:
        """Compile the serving programs with throwaway requests so the
        first real request does not pay compile time (benches call this
        before starting their clocks): each prefill row count P in
        1..prefill_batch (retraced per P), the greedy decode fast path,
        AND the sampled decode path (one tempered request).  Telemetry
        is suppressed for the duration — no lifecycle events, no
        TTFT/TPOT observations — so dashboards never see the throwaway
        requests or their compile-dominated latencies."""
        events, self.events = self.events, ev_mod.NULL
        self._warming = True
        try:
            for p in range(1, min(self.cfg.prefill_batch,
                                  self.cfg.max_batch) + 1):
                rids = [
                    self.submit(np.zeros((1,), np.int32), 2)
                    for _ in range(p)
                ]
                self.run_until_drained()
                for rid in rids:
                    del self.results[rid]
            rid = self.submit(
                np.zeros((1,), np.int32), 2,
                sampling=SamplingParams(
                    temperature=0.5, top_k=2, top_p=0.9
                ),
            )
            self.run_until_drained()
            del self.results[rid]
        finally:
            self.events = events
            self._warming = False
        self.audit.clear()
        self.step_count = 0
        self.steps_with_decode = 0
        self.steps_with_prefill = 0
