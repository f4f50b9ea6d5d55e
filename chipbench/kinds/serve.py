"""Traffic kinds ``serve-open`` and ``serve-closed``: `ServeEngine` driven
step by step from one thread.

Open loop: requests are submitted when due, whatever the engine's state,
and every time is taken from when the request was DUE, on the harness's
clock; how late the generator ran is reported.  Closed loop: as many
clients as the traffic file says, each sending its next request when its
last completes; the pool is filled during set-up.  The harness stamps
first and last tokens itself after each engine step, by watching the
requests' token lists grow; it reads no latency the program computed.
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass

import numpy as np

from chipbench import correct, device as device_mod, schedule
from chipbench.arithmetic import percentile
from chipbench.harness import Outcome, RunContext, log, seed_key


@dataclass
class Track:
    plan: schedule.PlannedRequest
    req: object                    # the engine's own record of the request
    due: float
    submitted: float
    admitted: float | None = None
    slot: int = -1                 # the decode slot it was admitted to
    first: float | None = None
    last: float | None = None
    seen: int = 0
    seen_at_window_start: int = 0
    finished: float | None = None


class Driver:
    """The step loop both kinds share."""

    def __init__(self, ctx: RunContext, engine):
        self.ctx, self.engine, self.rec = ctx, engine, ctx.rec
        self.live: list[Track] = []
        self.done: list[Track] = []
        self.clock = time.perf_counter

    def submit(self, plan: schedule.PlannedRequest, due: float) -> Track:
        self.engine.submit(plan.prompt, plan.max_new)
        tr = Track(plan=plan, req=self.engine.queue[-1], due=due, submitted=self.clock())
        self.live.append(tr)
        return tr

    def step(self) -> list[Track]:
        """One engine step inside a span; returns the requests it finished."""
        e = self.engine
        before = (e.steps_with_prefill, e.steps_with_decode)
        decoding = int(e.active.sum())
        held = int(e.index[e.active].sum())
        in_pool = int(sum(e.index[s] for s, r in enumerate(e.slots) if r is not None))
        with self.rec.span("engine_step") as sp:
            e.step()
        sp.attrs.update(
            prefill=e.steps_with_prefill > before[0],
            decode=e.steps_with_decode > before[1],
            occupancy=e.occupancy(), decoding_slots=decoding, held_tokens=held,
            pool_tokens=in_pool,
        )
        finished, still = [], []
        for tr in self.live:
            if tr.admitted is None and tr.req.state != "queued":
                tr.admitted = sp.start  # admission runs at the step's start
                tr.slot = tr.req.slot
            n = len(tr.req.tokens)
            if n > tr.seen:
                if tr.first is None:
                    tr.first = sp.end
                tr.seen, tr.last = n, sp.end
            if tr.req.state == "finished":
                tr.finished = sp.end
                finished.append(tr)
            else:
                still.append(tr)
        self.live = still
        self.done.extend(finished)
        return finished


# the device programs these kinds run, by the start of their names
PROGRAMS = ("serve_decode_greedy", "serve_prefill")


def engine_config(sc: dict):
    """The engine's sizing from a configuration's ``serve`` section: every
    key of it that is a field of `ServeConfig`, and the cache in ``dtype``."""
    import dataclasses

    import jax.numpy as jnp

    from tpu_dist.serve import ServeConfig

    fields = {f.name for f in dataclasses.fields(ServeConfig)}
    return ServeConfig(**{k: v for k, v in sc.items() if k in fields},
                       cache_dtype=jnp.dtype(sc["dtype"]))


def _build_engine(ctx: RunContext):
    from tpu_dist.serve import ServeEngine

    sc, model = ctx.cell.config["serve"], ctx.cell.config
    lm = ctx.cell.family.make_lm(model, seed_key(ctx.seed), sc["dtype"])
    params, _ = lm.init()
    engine = ServeEngine(lm, params, engine_config(sc), now=time.perf_counter)
    # Warm the cell's own programs and no others: a prefill round of each
    # row count up to prefill_batch, and the greedy decode step.  (The
    # traffic is greedy, so `ServeEngine.warmup()`'s sampled-decode
    # program is never run and not compiled here.)
    for rows in range(1, min(sc["prefill_batch"], sc["max_batch"]) + 1):
        for _ in range(rows):
            engine.submit(np.zeros((1,), np.int32), 2)
        engine.run_until_drained()
    engine.results.clear()
    return engine


def run(ctx: RunContext) -> Outcome:
    engine = _build_engine(ctx)
    drv = Driver(ctx, engine)
    kind = ctx.cell.traffic["kind"]
    out = (_open if kind == "serve-open" else _closed)(ctx, drv)
    devs = ctx.devices[:1]
    out.facts["hbm_peak_bytes"] = device_mod.memory_peak_bytes(devs)
    served = _sample_served(ctx, drv, out.facts.pop("window_start"))
    sc = ctx.cell.config["serve"]
    model = ctx.cell.config
    # the reference runs once the program's weights and pool are freed
    drv.engine = None
    del engine
    gc.collect()  # the engine's jitted steps close over it: a cycle
    verdict = correct.check_serving(
        ctx.cell.family, model, ctx.seed, served, dtype=sc["dtype"], pad_to=sc["max_seq"],
        device=devs[0], limits=ctx.cell.config["limits"]["serve"], control=ctx.control,
    )
    out.correct = verdict.ok and out.failed == 0
    out.facts["verdict"] = verdict
    return out


def _sample_served(ctx: RunContext, drv: Driver, window_start: float):
    """The requests the reference is run over: for every slot index that
    served in the window one request, drawn from the seed among those the
    slot finished (a wrong token confined to one slot's cache is then
    seen whichever slot it is), the longest of all, and more drawn from
    the seed up to the traffic file's ``check_requests``.  A slot that
    finished none in the window is stood for by the request it still holds,
    with the tokens served so far."""
    want = int(ctx.cell.traffic.get("check_requests", 6))
    fin = [t for t in drv.done if t.finished is not None and t.finished >= window_start
           and t.seen > 0]
    by_slot: dict[int, list[Track]] = {}
    for t in fin:
        by_slot.setdefault(t.slot, []).append(t)
    for t in drv.live:
        if t.seen > 0 and t.slot not in by_slot:
            by_slot[t.slot] = [t]
    if not by_slot:
        return []
    rng = np.random.default_rng([ctx.seed, 6])
    size = lambda t: t.plan.prompt.size + t.seen  # noqa: E731
    chosen = [group[int(rng.integers(len(group)))] for _, group in sorted(by_slot.items())]
    chosen.append(max((t for group in by_slot.values() for t in group), key=size))
    chosen.extend(fin[i] for i in rng.permutation(len(fin)))
    # the slots' own and the longest come first: cut the seeded others, and
    # drop what was drawn twice
    chosen = list({id(t): t for t in chosen}.values())[: max(want, len(by_slot) + 1)]
    log(f"reference sample: {len(chosen)} requests over {len(by_slot)} slot indices")
    return [(t.plan.prompt, np.asarray(t.req.tokens[: t.seen], np.int32)) for t in chosen]


def _step_facts(ctx: RunContext, start: float) -> dict:
    steps = ctx.rec.named("engine_step", since=start)
    sc = ctx.cell.config["serve"]
    max_batch, pool = sc["max_batch"], sc["num_blocks"] * sc["block_size"]
    # a stalled step is what a far-off tail comes from: say where it was
    slow = sorted(steps, key=lambda s: -s.ms)[:3]
    log("slowest engine steps: " + ", ".join(
        f"{s.ms:.0f} ms at {s.start - start:.1f} s" for s in slow))
    return {
        "prefill_step_ms": [s.ms for s in steps if s.attrs["prefill"]],
        "decode_step_ms": [s.ms for s in steps if s.attrs["decode"] and not s.attrs["prefill"]],
        "decode_held_tokens": [
            s.attrs["held_tokens"] for s in steps
            if s.attrs["decode"] and not s.attrs["prefill"]
        ],
        "decode_busy_slots": [
            s.attrs["decoding_slots"] for s in steps
            if s.attrs["decode"] and not s.attrs["prefill"]
        ],
        "slots_busy_share": [s.attrs["occupancy"] / max_batch for s in steps],
        "pool_held_share": [s.attrs["pool_tokens"] / pool for s in steps],
        "engine_steps": len(steps),
    }


def _open(ctx: RunContext, drv: Driver) -> Outcome:
    tr, model = ctx.cell.traffic, ctx.cell.config
    span = max(ctx.seconds - float(tr.get("drain_s", 0.0)), 0.5 * ctx.seconds)
    plan = schedule.open_loop(tr, ctx.seed, span, ctx.cell.family.vocab_size(model))
    setup_s = time.perf_counter() - ctx.t0
    compiles0 = ctx.compiles.value
    start = time.perf_counter()
    i = 0
    while True:
        now = time.perf_counter()
        if now - start >= ctx.seconds:
            break
        if ctx.tracer:
            ctx.tracer.maybe_start(now, start, ctx.seconds, tail_s=ctx.seconds - span)
        while i < len(plan) and start + plan[i].due_s <= now:
            drv.submit(plan[i], start + plan[i].due_s)
            i += 1
        if not drv.engine.pending:
            nxt = plan[i].due_s if i < len(plan) else ctx.seconds
            with ctx.rec.span("idle_wait"):
                time.sleep(max(0.0, min(nxt, ctx.seconds) - (time.perf_counter() - start)))
            continue
        drv.step()
    end = time.perf_counter()
    if ctx.tracer:
        ctx.tracer.stop(end)
    compiles = ctx.compiles.value - compiles0

    sent = drv.done + drv.live
    unsent = len(plan) - i
    scored = [t for t in sent if t.plan.scored]
    no_first = [t for t in sent if t.first is None]
    # a request with no first token sits in the tail at the window's end
    ttft = [((t.first if t.first is not None else end) - t.due) * 1e3 for t in scored]
    tpot = [(t.last - t.first) / (t.seen - 1) * 1e3 for t in scored if t.seen >= 2]
    emitted_wrong = [t for t in drv.done if t.seen != t.plan.max_new]
    e2e = {"setup_s": setup_s}
    if ttft:
        e2e["ttft_mean_ms"] = sum(ttft) / len(ttft)
    if tpot:
        e2e["tpot_mean_ms"] = sum(tpot) / len(tpot)
    if ttft and tpot:
        log(f"TTFT mean {e2e['ttft_mean_ms']:.1f} p50 {percentile(ttft, 50):.1f} "
            f"p90 {percentile(ttft, 90):.1f} ms; TPOT mean {e2e['tpot_mean_ms']:.1f} "
            f"p50 {percentile(tpot, 50):.1f} p90 {percentile(tpot, 90):.1f} ms")
    log(f"set-up {setup_s:.1f} s; window: {len(sent)} sent ({len(scored)} scored, {unsent} not yet due), "
        f"{len(drv.done)} finished, {len(no_first)} without a first token, "
        f"{compiles} compilations")
    facts = _step_facts(ctx, start)
    facts.update(
        window_start=start,
        generator_late_ms=[(t.submitted - t.due) * 1e3 for t in sent],
        queue_wait_ms=[((t.admitted if t.admitted is not None else end) - t.due) * 1e3
                       for t in scored],
        ttft_ms=ttft, tpot_ms=tpot, compiles_in_window=compiles,
    )
    return Outcome(
        end_to_end=e2e, attempted=len(plan),
        failed=len(no_first) + unsent + len(emitted_wrong), correct=False, facts=facts,
    )


def _closed(ctx: RunContext, drv: Driver) -> Outcome:
    tr, model = ctx.cell.traffic, ctx.cell.config
    plan = schedule.closed_loop(tr, ctx.seed, ctx.cell.family.vocab_size(model))
    nxt = [0] * len(plan)

    def send(client: int) -> None:
        mine = plan[client]
        drv.submit(mine[nxt[client] % len(mine)], time.perf_counter())
        nxt[client] += 1

    def step() -> None:
        for t in drv.step():
            send(t.plan.client)

    for c in range(len(plan)):
        send(c)
    # fill the pool: step until every client's first request has prefilled
    first = list(drv.live)
    while any(t.first is None for t in first):
        step()
    setup_s = time.perf_counter() - ctx.t0
    compiles0 = ctx.compiles.value
    for t in drv.live:
        t.seen_at_window_start = t.seen
    done_before = len(drv.done)
    start = now = time.perf_counter()
    while now - start < ctx.seconds:
        if ctx.tracer:
            ctx.tracer.maybe_start(now, start, ctx.seconds)
        step()
        now = time.perf_counter()
    if ctx.tracer:
        ctx.tracer.stop(now)
    elapsed = now - start
    compiles = ctx.compiles.value - compiles0
    in_window = drv.done[done_before:] + drv.live
    tokens = sum(t.seen - t.seen_at_window_start for t in in_window)
    wrong = [t for t in drv.done[done_before:] if t.seen != t.plan.max_new]
    log(f"set-up {setup_s:.1f} s; window: {tokens} tokens in {elapsed:.3f} s over {len(in_window)} requests, "
        f"{len(drv.done) - done_before} finished, {compiles} compilations")
    facts = _step_facts(ctx, start)
    facts.update(window_start=start, compiles_in_window=compiles)
    return Outcome(
        end_to_end={"setup_s": setup_s, "serve_out_tokens_per_s": tokens / elapsed},
        attempted=len(in_window), failed=len(wrong), correct=False, facts=facts,
    )
