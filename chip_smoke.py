"""chip_smoke.py — does the main path start, compile, shard and answer
correctly on the TPU?

    python chip_smoke.py              # on a TPU machine (through the chip tool)
    python chip_smoke.py --rehearse   # toy sizes, Pallas interpreted, any backend

One process, normal entry points only (`comm.spmd`, `train.Trainer`,
`train.LMTrainer`, `serve.ServeEngine`, `ops.*`), at the full width the
repo benchmarks (depth is the model's own 12; weights random from a
seed).  It claims no speed: the step times it prints are information for
whoever writes the benchmark, under no metric's name.

Contract: without ``--rehearse`` it exits non-zero BEFORE compiling
anything unless ``jax.devices()[0].platform == "tpu"``; every phase that
raises or fails a check makes the exit code non-zero; a phase that needs
more chips than the machine has is printed as ``skipped: needs N devices,
have M`` (a fact about the machine, never a caught error).  The last
stdout line is the result, one JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``,
``ok`` true only when every phase that was not skipped passed.  The line
before it, ``chip_smoke summary {...}``, carries versions, cache counts
and the per-phase report (also written to ``chiprun_out/``).
``--rehearse`` is how the command is debugged off-chip
(``JAX_PLATFORMS=cpu``, optionally
``XLA_FLAGS=--xla_force_host_platform_device_count=4``): its summary says
``"rehearsal": true`` and it never prints the result line.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import threading
import time
import traceback
from dataclasses import dataclass

import numpy as np

DEADLINE_S = 1150  # the contract allows 1200 s, compilation included
OUT_DIR = "chiprun_out"  # the one directory this script writes under


@dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the rehearsal."""

    vocab: int = 32768
    dim: int = 768
    depth: int = 12
    heads: int = 12
    max_seq: int = 2048
    lm_batch: int = 8  # benchmarks/lm_train.py's 8x2048 case
    lm_seq: int = 2048
    lm4_batch: int = 16
    ring_elems: int = 1 << 20
    mnist_samples: int = 4096
    serve_max_batch: int = 8
    serve_block: int = 16
    serve_blocks: int = 512
    serve_max_seq: int = 1024
    serve_chunk: int = 128
    serve_prefill_batch: int = 4
    serve_prompts: tuple = (32, 64, 100, 128, 200, 300, 400, 512)
    serve_new: tuple = (32, 40, 48, 56, 64, 36, 44, 60)
    matmuls: tuple = ((1024, 1024, 1024, "none"), (4096, 768, 3072, "gelu"))
    flash_heads: int = 12
    flash_seqs: tuple = (2048, 4096)
    flash_window: int = 512
    ring_kernel_shape: tuple = (256, 512)
    interpret: bool = False


FULL = Sizes()
TOY = Sizes(
    vocab=512, dim=64, depth=2, heads=4, max_seq=256, lm_batch=4,
    lm_seq=128, lm4_batch=8, ring_elems=1 << 12, mnist_samples=1024,
    serve_max_batch=4, serve_block=8, serve_blocks=64, serve_max_seq=128,
    serve_chunk=16, serve_prefill_batch=2,
    serve_prompts=(4, 9, 16, 17, 24, 31, 33, 40),
    serve_new=(4, 5, 6, 7, 8, 4, 5, 6),
    matmuls=((256, 256, 256, "none"), (256, 128, 384, "gelu")),
    flash_heads=2, flash_seqs=(256, 512), flash_window=128,
    ring_kernel_shape=(8, 128), interpret=True,
)

SEED = 1234  # LMTrainConfig's default seed: every trainer starts equal


class CheckFailed(Exception):
    """A phase ran and its result was wrong."""


class Skipped(Exception):
    """A phase needs more devices than the machine has."""

    def __init__(self, needs: int, have: int):
        super().__init__(f"needs {needs} devices, have {have}")


def check(cond, msg: str) -> None:
    if not cond:
        raise CheckFailed(msg)


def say(msg: str) -> None:
    print(msg, flush=True)


class Watchdog:
    """A hang must not outlive the contract: whatever is still running at
    the deadline is named on stderr and the process exits 124.  The main
    thread may be blocked inside the runtime (a kernel waiting on a
    semaphore that never fires), so this is a timer thread and
    ``os._exit``, not an exception."""

    def __init__(self, deadline_s: float):
        self._end = time.monotonic() + deadline_s
        self._timer: threading.Timer | None = None
        self._arm(deadline_s, "the whole run")

    def _arm(self, seconds: float, what: str) -> None:
        if self._timer is not None:
            self._timer.cancel()
        seconds = max(0.0, min(seconds, self._end - time.monotonic()))
        self._timer = threading.Timer(seconds, self._fire, (what,))
        self._timer.daemon = True
        self._timer.start()

    @staticmethod
    def _fire(what: str) -> None:
        print(f"chip_smoke: {what} did not finish in time — aborting",
              file=sys.stderr, flush=True)
        os._exit(124)

    @contextlib.contextmanager
    def within(self, seconds: float, what: str):
        """``what`` gets ``seconds``, then the run's own deadline applies
        again."""
        self._arm(seconds, what)
        try:
            yield
        finally:
            self._arm(self._end - time.monotonic(), "the whole run")

    def cancel(self) -> None:
        self._timer.cancel()


class CompileCounter:
    """Programs lowered by this process (one per new jit signature —
    whether XLA then compiles it or the persistent cache serves it)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1


@dataclass
class Ctx:
    sizes: Sizes
    devices: list
    compiles: CompileCounter
    watchdog: Watchdog
    # set by mnist_dp's 1-chip run for the all-chip comparison
    mnist_first_loss: float | None = None

    @property
    def on_tpu(self) -> bool:
        return self.devices[0].platform == "tpu"


def rel_err(got, want) -> float:
    """max|got - want| over max|want| — one number per comparison, sized
    to the reference's own scale (bf16 keeps ~3 decimal digits)."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-30))


def peak_hbm_mb(device) -> float | None:
    from tpu_dist.train.metrics import device_memory_stats

    stats = device_memory_stats(device) or {}
    peak = stats.get("peak_bytes_in_use")
    return round(peak / 1e6, 1) if peak else None


# ------------------------------------------------------------------ phases


def phase_device(ctx: Ctx) -> dict:
    import shutil

    from tpu_dist.observe import memory
    from tpu_dist.train import flops

    dev = ctx.devices[0]
    spec = flops.chip_spec(dev)  # raises on a TPU kind not in the table
    snap = memory.memory_snapshot(dev)
    say(f"  peaks[{dev.device_kind!r}] = {spec}")
    say(f"  memory: {snap}")
    if ctx.on_tpu:
        check(spec is not None, f"no peaks entry for {dev.device_kind!r}")
        check(snap["source"] == "hbm",
              f"memory source is {snap['source']!r}, not the device's HBM")
    info = {"device_kind": dev.device_kind, "memory_source": snap["source"],
            "bytes_limit": snap["bytes_limit"]}
    # The native runtime builds lazily from the tracked .cc files; nothing
    # below needs it (synthetic MNIST, one process), so a machine without
    # a compiler only loses this line.
    if shutil.which("make") and shutil.which("g++"):
        from tpu_dist import runtime

        t0 = time.perf_counter()
        port = runtime.free_port()
        check(0 < port < 65536, f"native free_port returned {port}")
        info["native_runtime"] = "built"
        say(f"  native runtime: built + loaded in "
            f"{time.perf_counter() - t0:.1f}s (free_port -> {port})")
    else:
        info["native_runtime"] = "no make/g++ on this machine"
        say("  native runtime: no make/g++ on this machine — not built")
    return info


def phase_collectives(ctx: Ctx) -> dict:
    import jax.numpy as jnp
    from jax import lax

    from tpu_dist import comm, parallel

    n = len(ctx.devices)
    info: dict = {"world": n}
    t0 = time.perf_counter()

    if n >= 2:
        def pingpong():  # demos/ptp.py's body, the paper's first exercise
            rank = comm.rank()
            t = jnp.zeros(1)
            t = comm.send(jnp.where(rank == 0, t + 1, t), dst=1, src=0)
            ping = t
            t = comm.send(jnp.where(rank == 1, t + 1, t), dst=0, src=1)
            return ping, t

        ping, pong = comm.spmd(pingpong, world=n)
        ping, pong = np.asarray(ping)[:, 0], np.asarray(pong)[:, 0]
        say(f"  ping {ping[:2].tolist()} pong {pong[:2].tolist()}")
        check(ping[0] == 1.0 and ping[1] == 1.0, f"ping != 1.0: {ping}")
        check(pong[0] == 2.0 and pong[1] == 2.0, f"pong != 2.0: {pong}")
    else:
        say(f"  send/recv ping-pong skipped: needs 2 devices, have {n}")
        info["pingpong"] = f"skipped: needs 2 devices, have {n}"

    total = np.asarray(comm.spmd(lambda: comm.all_reduce(jnp.ones(())), world=n))
    say(f"  all_reduce(ones) -> {total.tolist()}")
    check((total == n).all(), f"all_reduce(ones) != {n}: {total}")

    # Integer-valued f32 payload: every partial sum is exact, so the two
    # rings must equal psum ELEMENTWISE whatever order they add in.
    elems = ctx.sizes.ring_elems
    base = jnp.arange(elems, dtype=jnp.float32) % 1024.0

    def rings(x):
        mine = x + comm.rank()
        return (
            lax.psum(mine, comm.DEFAULT_AXIS),
            parallel.ring_all_reduce(mine),
            parallel.ring_all_reduce_chunked(mine),
        )

    info["setup_s"] = round(time.perf_counter() - t0, 2)
    t1 = time.perf_counter()
    want, ring, chunked = (np.asarray(a) for a in comm.spmd(rings, base, world=n))
    info["run_s"] = round(time.perf_counter() - t1, 2)
    expect = n * (np.arange(elems) % 1024) + n * (n - 1) // 2
    check(np.array_equal(want[0], expect), "psum is not the known answer")
    check(np.array_equal(ring, want), "ring_all_reduce != psum")
    check(np.array_equal(chunked, want), "ring_all_reduce_chunked != psum")
    say(f"  ring + chunked ring == psum elementwise over {elems} f32 "
        f"x {n} ranks")
    return info


def _mnist_run(ctx: Ctx, devices: list) -> tuple[float, list, object]:
    """One Trainer on ``devices``: a probe step on a FIXED batch (the
    first-step loss the all-chip run is compared on), then ``fit``."""
    import jax
    import jax.numpy as jnp

    from tpu_dist import comm, data, models, parallel, train

    n = len(devices)
    mesh = comm.make_mesh(n, ("data",), mesh_devices=devices)
    cfg = train.TrainConfig(epochs=2, global_batch=128, log=lambda m: say(f"  {m}"))
    trainer = train.Trainer(models.mnist_net(), models.IN_SHAPE, mesh, cfg)
    ds = data.load_mnist("train", synthetic_size=ctx.sizes.mnist_samples)
    x = np.stack([ds[i][0] for i in range(cfg.global_batch)])
    y = np.asarray([ds[i][1] for i in range(cfg.global_batch)], np.int32)
    batch = parallel.shard_batch((jnp.asarray(x), jnp.asarray(y)), mesh)
    (trainer.params, trainer.model_state, trainer.opt_state, loss, _) = (
        trainer.step(trainer.params, trainer.model_state, trainer.opt_state,
                     batch, jax.random.key(0))
    )
    first = float(loss)
    history = trainer.fit(ds, epochs=2)
    return first, history, trainer


def phase_mnist_dp(ctx: Ctx) -> dict:
    import jax

    n = len(ctx.devices)
    info: dict = {}
    runs = [("1chip", ctx.devices[:1])]
    if n > 1:
        runs.append((f"{n}chip", ctx.devices))
    for name, devs in runs:
        t0 = time.perf_counter()
        first, hist, trainer = _mnist_run(ctx, devs)
        dt = time.perf_counter() - t0
        losses = [h.mean_loss for h in hist]
        say(f"  [{name}] first-step loss {first:.6f}, epoch losses "
            f"{[round(v, 4) for v in losses]} ({dt:.1f}s)")
        check(all(math.isfinite(v) for v in [first] + losses),
              f"[{name}] non-finite loss")
        check(losses[1] < losses[0],
              f"[{name}] epoch 2 loss {losses[1]} not below epoch 1 {losses[0]}")
        info[name] = {"first_loss": first, "epoch_losses": losses,
                      "seconds": round(dt, 2)}
        if len(devs) == 1:
            ctx.mnist_first_loss = first
            continue
        check(abs(first - ctx.mnist_first_loss) < 1e-5,
              f"[{name}] first-step loss {first} != 1-chip "
              f"{ctx.mnist_first_loss}")
        for leaf in jax.tree.leaves(trainer.params):
            shards = leaf.addressable_shards
            check(len({s.device for s in shards}) == len(devs),
                  f"[{name}] a param leaf is on {len(shards)} devices")
            ref = np.asarray(shards[0].data)
            check(ref.shape == leaf.shape, f"[{name}] dp leaf is not a replica")
            for s in shards[1:]:
                check(np.array_equal(ref, np.asarray(s.data)),
                      f"[{name}] replicas differ on {s.device}")
        say(f"  [{name}] {len(devs)} bit-identical replicas; first-step "
            f"loss equals the 1-chip run's")
    if n == 1:
        info["all_chips"] = "skipped: needs 2 devices, have 1"
        say("  all-chip run skipped: needs 2 devices, have 1")
    return info


def _lm(ctx: Ctx):
    from tpu_dist import models

    s = ctx.sizes
    return models.TransformerLM(
        vocab=s.vocab, dim=s.dim, depth=s.depth, heads=s.heads,
        max_seq=s.max_seq, pos_embedding="rope",
    )


def _tokens(ctx: Ctx, batch: int, seq: int):
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    return jnp.asarray(
        rng.integers(0, ctx.sizes.vocab, (batch, seq), dtype=np.int64),
        jnp.int32,
    )


def _lm_steps(ctx, trainer, batch, warmup: int, steps: int) -> dict:
    """``warmup`` steps (set-up: compile included), then ``steps`` timed
    steps closed by a host readback; counts programs lowered inside the
    timed window (expect 0).  ``steps=0``: the first loss only."""
    import jax

    from tpu_dist.utils.platform import host_sync

    key = jax.random.key(0)
    p, ms, os_ = trainer.params, trainer._model_state, trainer.opt_state
    t0 = time.perf_counter()
    first = None
    for _ in range(warmup):
        p, ms, os_, loss, _ = trainer.step(p, ms, os_, batch, key)
        if first is None:
            first = host_sync(loss)
    host_sync(loss)
    setup_s = time.perf_counter() - t0
    out = {"first_loss": first, "setup_s": round(setup_s, 2)}
    if steps:
        c0 = ctx.compiles.count
        t1 = time.perf_counter()
        for _ in range(steps):
            p, ms, os_, loss, _ = trainer.step(p, ms, os_, batch, key)
        out["last_loss"] = host_sync(loss)
        run_s = time.perf_counter() - t1
        out.update(run_s=round(run_s, 2),
                   step_ms=round(run_s / steps * 1e3, 2),
                   compiles_in_window=ctx.compiles.count - c0)
    trainer.params, trainer.opt_state = p, os_
    return out


def phase_lm_train_1chip(ctx: Ctx) -> dict:
    import jax

    from tpu_dist import comm, parallel, train

    s = ctx.sizes
    mesh = comm.make_mesh(1, ("data",), mesh_devices=ctx.devices[:1])
    trainer = train.LMTrainer(
        _lm(ctx), mesh,
        train.LMTrainConfig(global_batch=s.lm_batch,
                            compute_dtype="bfloat16", log=say),
    )
    batch = parallel.shard_batch((_tokens(ctx, s.lm_batch, s.lm_seq),), mesh)
    info = _lm_steps(ctx, trainer, batch, warmup=2, steps=5)
    # The compiled step itself (persistent-cache hit of the program
    # the warm-up just compiled): did the program pick flash?
    step = trainer._partition.step
    hlo = step.lower(
        trainer.params, trainer.opt_state, batch, jax.random.key(0)
    ).compile().as_text()
    info["pallas_custom_calls"] = hlo.count("tpu_custom_call")
    info["tokens_per_s"] = round(s.lm_batch * s.lm_seq / (info["step_ms"] / 1e3))
    info["peak_hbm_mb"] = peak_hbm_mb(ctx.devices[0])
    say(f"  {s.lm_batch}x{s.lm_seq} dim {s.dim} depth {s.depth} bf16+flash: "
        f"set-up {info['setup_s']}s, {info['step_ms']} ms/step, "
        f"{info['tokens_per_s']} tokens/s, peak HBM so far {info['peak_hbm_mb']} MB, "
        f"loss {info['first_loss']:.4f} -> {info['last_loss']:.4f}, "
        f"{info['pallas_custom_calls']} Pallas custom calls in the HLO, "
        f"{info['compiles_in_window']} programs lowered in the timed window")
    check(math.isfinite(info["first_loss"]) and math.isfinite(info["last_loss"]),
          "non-finite loss")
    check(abs(info["first_loss"] - math.log(s.vocab)) < 0.3,
          f"first loss {info['first_loss']} is not ~ln {s.vocab} = "
          f"{math.log(s.vocab):.3f}")
    check(info["compiles_in_window"] == 0,
          f"{info['compiles_in_window']} programs lowered during the timed steps")
    if ctx.on_tpu:
        # forward + dK/dV + dQ kernels per layer, fused into one scan or
        # unrolled: at least the three distinct kernels must be there
        check(info["pallas_custom_calls"] >= 3,
              "the compiled step has no Pallas TPU custom call: the dense "
              "form ran, not flash")
    return info


def _placement_checks(name: str, trainer, devices: list) -> dict:
    """State is where the rules say: every leaf on all four devices, every
    leaf the rules shard really split (not whole on device 0), per-chip
    bytes as `parallel.per_device_bytes` counts them equal to what the
    shardings promise, and every chip's allocator holding bytes."""
    import jax
    from jax.sharding import PartitionSpec as P

    from tpu_dist import parallel

    part = trainer._partition
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    n_sharded = 0
    promised = 0
    for tree, specs in ((trainer.params, part.param_specs),
                        (trainer.opt_state, part.opt_specs)):
        leaves = jax.tree.leaves(tree)
        spec_leaves = jax.tree.leaves(specs, is_leaf=is_spec)
        check(len(leaves) == len(spec_leaves), "spec tree != state tree")
        for leaf, spec in zip(leaves, spec_leaves):
            shards = leaf.addressable_shards
            check({s.device for s in shards} == set(devices),
                  f"[{name}] a leaf spans {len(shards)} devices, not 4")
            local = leaf.sharding.shard_shape(leaf.shape)
            promised += int(np.prod(local)) * leaf.dtype.itemsize
            if any(e is not None for e in tuple(spec)):
                n_sharded += 1
                check(tuple(local) != tuple(leaf.shape),
                      f"[{name}] spec {spec} left a leaf whole on each device")
                check(len({str(s.index) for s in shards}) > 1,
                      f"[{name}] spec {spec}: every shard holds the same slice")
    check(n_sharded > 0, f"[{name}] the rule set sharded nothing")
    per_chip = {}
    for d in devices:
        counted = parallel.per_device_bytes(
            (trainer.params, trainer.opt_state), device=d
        )
        check(counted == promised,
              f"[{name}] device {d.id}: per_device_bytes {counted} != "
              f"{promised} promised by the shardings")
        stats = d.memory_stats()
        in_use = stats["bytes_in_use"] if stats else None
        if stats is not None:
            check(in_use >= counted,
                  f"[{name}] device {d.id}: bytes_in_use {in_use} < its "
                  f"{counted} bytes of state")
        per_chip[d.id] = in_use
    return {"sharded_leaves": n_sharded, "state_bytes_per_chip": promised,
            "bytes_in_use": per_chip}


def phase_lm_train_4chip(ctx: Ctx) -> dict:
    from tpu_dist import comm, parallel, train

    n = len(ctx.devices)
    if n < 4:
        raise Skipped(4, n)
    s = ctx.sizes
    devs = ctx.devices[:4]
    toks = _tokens(ctx, s.lm4_batch, s.lm_seq)
    info: dict = {}
    # The reference: ONE chip, same batch, same seed.  16x2048 does not
    # fit one chip's activations, so it runs as two accumulated
    # microbatches of 8 — the same mean loss over the same 16 rows — with
    # flash, as in lm_train_1chip (dense scores at 16x2048 need ~16 GB).
    mesh1 = comm.make_mesh(1, ("data",), mesh_devices=devs[:1])
    ref_trainer = train.LMTrainer(
        _lm(ctx), mesh1,
        train.LMTrainConfig(global_batch=s.lm4_batch, accum_steps=2,
                            compute_dtype="bfloat16", log=say),
    )
    ref = _lm_steps(ctx, ref_trainer,
                    parallel.shard_batch((toks,), mesh1), warmup=1, steps=0)
    say(f"  [1-chip reference, accum 2, flash] first loss "
        f"{ref['first_loss']:.5f} (set-up {ref['setup_s']}s)")
    del ref_trainer
    # The engine's step is one GSPMD program, which a Mosaic kernel
    # cannot be part of ("cannot be automatically partitioned"): where
    # flash takes the length, attention is each chip's share of batch and
    # heads inside one `shard_map` over the rule set's axes
    # (`nn.dot_product_attention`, PERF.md section 3), else dense.
    for spec in ("dp=2,fsdp=2", "dp=2,tp=2"):
        mesh = parallel.build_mesh(spec, mesh_devices=devs)
        trainer = train.LMTrainer(
            _lm(ctx), mesh,
            train.LMTrainConfig(global_batch=s.lm4_batch, mesh_axes=spec,
                                compute_dtype="bfloat16", log=say),
        )
        batch = parallel.shard_batch((toks,), mesh, spec=trainer._batch_spec)
        row = _lm_steps(ctx, trainer, batch, warmup=1, steps=2)
        row["tokens_per_s"] = round(
            s.lm4_batch * s.lm_seq / (row["step_ms"] / 1e3)
        )
        row["peak_hbm_mb"] = peak_hbm_mb(devs[0])
        row.update(_placement_checks(spec, trainer, devs))
        say(f"  [{spec}] first loss {row['first_loss']:.5f} -> "
            f"{row['last_loss']:.5f}; set-up {row['setup_s']}s, "
            f"{row['step_ms']} ms/step, {row['tokens_per_s']} tokens/s, "
            f"peak HBM so far {row['peak_hbm_mb']} MB/chip; "
            f"{row['sharded_leaves']} sharded leaves, "
            f"{row['state_bytes_per_chip'] / 1e6:.1f} MB state/chip, "
            f"bytes_in_use {row['bytes_in_use']}")
        check(math.isfinite(row["first_loss"])
              and math.isfinite(row["last_loss"]), f"[{spec}] non-finite loss")
        check(abs(row["first_loss"] - ref["first_loss"]) < 0.05,
              f"[{spec}] first loss {row['first_loss']} vs 1-chip "
              f"{ref['first_loss']}: beyond bf16 tolerance")
        info[spec] = row
        del trainer
    info["reference_first_loss"] = ref["first_loss"]
    return info


def phase_serve(ctx: Ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from tpu_dist import serve

    s = ctx.sizes
    lm = _lm(ctx)
    params, _ = lm.init(jax.random.key(SEED))
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    engine = serve.ServeEngine(
        lm, params,
        serve.ServeConfig(
            max_batch=s.serve_max_batch, block_size=s.serve_block,
            num_blocks=s.serve_blocks, max_seq=s.serve_max_seq,
            prefill_chunk=s.serve_chunk, prefill_batch=s.serve_prefill_batch,
        ),
    )
    t0 = time.perf_counter()
    engine.warmup()
    setup_s = time.perf_counter() - t0
    rng = np.random.default_rng(1)
    c0 = ctx.compiles.count
    want = {}
    t1 = time.perf_counter()
    for i, (plen, new) in enumerate(zip(s.serve_prompts, s.serve_new)):
        sampling = (
            serve.SamplingParams(temperature=0.8, top_k=40, top_p=0.95, seed=i)
            if i in (2, 5) else None
        )
        rid = engine.submit(rng.integers(0, s.vocab, plen), new, sampling=sampling)
        want[rid] = new
    results = engine.run_until_drained()
    run_s = time.perf_counter() - t1
    emitted = 0
    for rid, new in want.items():
        check(rid in results, f"request {rid} never finished")
        r = results[rid]
        check(r.emitted == new, f"request {rid}: emitted {r.emitted} != {new}")
        check(((0 <= r.tokens) & (r.tokens < s.vocab)).all(),
              f"request {rid}: token out of range")
        emitted += r.emitted
    compiles_after_warmup = ctx.compiles.count - c0

    # Paged path vs the dense cache on one prompt's prefill: logits, not
    # tokens — a random-init model's near-flat logits flip argmax on
    # rounding, closeness is what the two paths owe each other.
    plen, bs = s.serve_prompts[0], s.serve_block
    nblk = math.ceil(plen / bs)
    toks = jnp.asarray(rng.integers(0, s.vocab, (1, plen)), jnp.int32)
    pos = jnp.arange(plen, dtype=jnp.int32)[None]
    paged, _ = jax.jit(
        lambda p, t, c, bt, ps, m: serve.paged_apply_cached(
            lm, p, t, c, bt, ps, m, bs)
    )(params, toks, serve.init_paged_cache(lm, nblk, bs, jnp.bfloat16),
      jnp.arange(nblk, dtype=jnp.int32)[None], pos, jnp.ones((1, plen), bool))
    dense, _ = jax.jit(lm.apply_cached)(
        params, toks, lm.init_cache(1, nblk * bs, jnp.bfloat16), jnp.int32(0)
    )
    err = rel_err(paged[0, -1], dense[0, -1])
    check(np.isfinite(np.asarray(paged[0, -1], np.float32)).all(),
          "paged logits not finite")
    check(err < 5e-2, f"paged vs dense first-token logits differ: rel {err:.3g}")
    info = {"setup_s": round(setup_s, 2), "run_s": round(run_s, 2),
            "requests": len(want), "tokens_emitted": emitted,
            "engine_steps": engine.step_count,
            "compiles_after_warmup": compiles_after_warmup,
            "paged_vs_dense_rel_err": err,
            "peak_hbm_mb": peak_hbm_mb(ctx.devices[0])}
    say(f"  warmup {info['setup_s']}s; {len(want)} requests, {emitted} tokens "
        f"in {info['run_s']}s over {engine.step_count} engine steps; "
        f"{compiles_after_warmup} programs lowered after warmup (reported, "
        f"not gated); paged vs dense logits rel err {err:.2e}; "
        f"peak HBM so far {info['peak_hbm_mb']} MB")
    return info


def phase_kernels(ctx: Ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from tpu_dist import comm, nn, ops

    s = ctx.sizes
    interp = s.interpret  # passed EXPLICITLY to every kernel
    info: dict = {"interpret": interp}
    key = jax.random.key(0)

    for m, k, n, epilogue in s.matmuls:
        k1, k2, k3, key = jax.random.split(key, 4)
        x = (jax.random.normal(k1, (m, k)) / math.sqrt(k)).astype(jnp.bfloat16)
        w = jax.random.normal(k2, (k, n)).astype(jnp.bfloat16)
        b = jax.random.normal(k3, (n,)).astype(jnp.bfloat16)
        t0 = time.perf_counter()
        got = ops.matmul(x, w, b, epilogue=epilogue, interpret=interp)
        got.block_until_ready()
        dt = time.perf_counter() - t0
        with jax.default_matmul_precision("float32"):
            pre = x.astype(jnp.float32) @ w.astype(jnp.float32) + b.astype(jnp.float32)
        want = jax.nn.gelu(pre) if epilogue == "gelu" else pre
        err = rel_err(got, want)
        say(f"  matmul {m}x{k}x{n} bf16 +bias {epilogue}: rel err {err:.2e} "
            f"(first call {dt:.1f}s)")
        check(err < 2e-2, f"matmul {m}x{k}x{n} {epilogue}: rel err {err}")
        info[f"matmul_{m}x{k}x{n}_{epilogue}"] = err

    for seq in s.flash_seqs:
        for window in (None, s.flash_window):
            kq, kk, kv, kw, key = jax.random.split(key, 5)
            shape = (1, s.flash_heads, seq, 64)
            q, kx, v = (jax.random.normal(r, shape).astype(jnp.bfloat16)
                        for r in (kq, kk, kv))
            wgt = jax.random.normal(kw, shape)  # non-trivial cotangent

            def flash_loss(q, kx, v):
                out = ops.flash_attention(
                    q, kx, v, causal=True, window=window, interpret=interp)
                return jnp.sum(out.astype(jnp.float32) * wgt), out

            def dense_loss(q, kx, v):
                out = nn.attention.dense_attention(
                    q, kx, v, causal=True, window=window)
                return jnp.sum(out * wgt), out

            t0 = time.perf_counter()
            (_, out), grads = jax.jit(
                jax.value_and_grad(flash_loss, argnums=(0, 1, 2), has_aux=True)
            )(q, kx, v)
            jax.block_until_ready(grads)
            dt = time.perf_counter() - t0
            f32 = [a.astype(jnp.float32) for a in (q, kx, v)]
            with jax.default_matmul_precision("float32"):
                (_, want), want_g = jax.jit(
                    jax.value_and_grad(dense_loss, argnums=(0, 1, 2), has_aux=True)
                )(*f32)
            errs = [rel_err(out, want)] + [
                rel_err(g, wg) for g, wg in zip(grads, want_g)
            ]
            tag = f"flash_S{seq}_causal" + (f"_w{window}" if window else "")
            say(f"  {tag} h{s.flash_heads} d64 bf16: rel err out/dq/dk/dv "
                f"{[f'{e:.1e}' for e in errs]} (first call {dt:.1f}s)")
            check(all(np.isfinite(e) for e in errs), f"{tag}: non-finite")
            check(errs[0] < 2e-2 and max(errs[1:]) < 4e-2,
                  f"{tag}: rel errs {errs} beyond bf16 tolerance")
            info[tag] = errs

    n = len(ctx.devices)
    if n >= 2:
        rows, cols = s.ring_kernel_shape
        base = (jnp.arange(rows * cols, dtype=jnp.float32) % 1024.0).reshape(rows, cols)

        def rdma(x):
            mine = x + 1000.0 * comm.rank()
            return (ops.ring_all_reduce_pallas(mine, interpret=interp),
                    lax.psum(mine, comm.DEFAULT_AXIS))

        t0 = time.perf_counter()
        # hand-written semaphores: the one place a bug is a hang, not an
        # exception — give it its own short leash
        with ctx.watchdog.within(180, "the RDMA ring kernel"):
            got, want = (np.asarray(a) for a in comm.spmd(rdma, base, world=n))
        say(f"  ring_all_reduce_pallas over {n} chips, {rows}x{cols} f32: "
            f"max |rdma - psum| = {np.abs(got - want).max()} "
            f"({time.perf_counter() - t0:.1f}s)")
        check(np.array_equal(got, want), "RDMA ring != psum")
        info["ring_all_reduce_pallas"] = "equal"
    else:
        say(f"  ring_all_reduce_pallas skipped: needs 2 devices, have {n}")
        info["ring_all_reduce_pallas"] = f"skipped: needs 2 devices, have {n}"
    return info


PHASES = (
    ("device", phase_device),
    ("collectives", phase_collectives),
    ("mnist_dp", phase_mnist_dp),
    ("lm_train_1chip", phase_lm_train_1chip),
    ("lm_train_4chip", phase_lm_train_4chip),
    ("serve", phase_serve),
    ("kernels", phase_kernels),
)


def run_phases(ctx: Ctx) -> dict:
    """Every phase runs; a failure is printed with its traceback, recorded
    as ``ok: false`` and fails the run — it is never downgraded."""
    report = {}
    for name, fn in PHASES:
        say(f"== {name}")
        t0 = time.perf_counter()
        try:
            info = fn(ctx)
            row = {"ok": True, **info}
        except Skipped as e:
            row = {"skipped": str(e)}
            say(f"  skipped: {e}")
        except Exception as e:  # recorded, reported, exit code non-zero
            traceback.print_exc()
            sys.stderr.flush()
            row = {"ok": False, "error": f"{type(e).__name__}: {e}"[:500]}
            say(f"  FAILED: {row['error']}")
        row["seconds"] = round(time.perf_counter() - t0, 2)
        report[name] = row
    return report


def result_line(ok: bool, dev, count: int) -> str:
    """The last stdout line of a real run: exactly ``ok`` and ``device``,
    the device as JAX reports it.  Everything else is in the summary line."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": int(count)},
    })


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse", action="store_true",
        help="toy sizes, Pallas in interpret mode, whatever backend JAX has; "
        "for debugging this command off-chip — never prints the pass line",
    )
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import jaxlib

    devices = jax.devices()
    dev = devices[0]
    if not args.rehearse and dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(devices)} device(s)).  Nothing was "
            "compiled.  Run it through the chip tool, or pass --rehearse to "
            "debug the command at toy sizes on this backend.",
            file=sys.stderr,
        )
        return 1

    watchdog = Watchdog(DEADLINE_S)

    from tpu_dist.observe import registry
    from tpu_dist.utils.platform import setup_compile_cache

    # the rehearsal compiles toy CPU programs nobody will ask for again
    cache_dir = None if args.rehearse else setup_compile_cache()
    try:
        import libtpu

        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    header = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu_version,
                     "python": sys.version.split()[0]},
        "compile_cache_dir": cache_dir,
    }
    say("chip_smoke " + json.dumps(header))

    ctx = Ctx(
        sizes=TOY if args.rehearse else FULL, devices=devices,
        compiles=CompileCounter(), watchdog=watchdog,
    )
    report = run_phases(ctx)
    watchdog.cancel()

    all_ok = all(r.get("ok", True) for r in report.values())
    counter = registry.REGISTRY.counter
    summary = {
        # "ok" exists only on a real run: a rehearsal can never print the
        # pass line, whatever its phases did
        **({"rehearsal": True, "rehearsal_ok": all_ok} if args.rehearse
           else {"ok": all_ok}),
        **header,
        "compile_cache": {
            "hits": int(counter("tpu_dist_compile_cache_hits_total").value()),
            "misses": int(counter("tpu_dist_compile_cache_misses_total").value()),
        },
        "programs_lowered": ctx.compiles.count,
        "seconds": round(time.perf_counter() - t_start, 1),
        "phases": report,
    }
    if not args.rehearse:
        os.makedirs(OUT_DIR, exist_ok=True)
        path = os.path.join(OUT_DIR, f"chip_smoke_{len(devices)}chip.json")
        with open(path, "w", encoding="utf-8") as fh:
            json.dump(summary, fh, indent=1, default=str)
    for name, row in report.items():
        state = ("skipped: " + row["skipped"] if "skipped" in row
                 else "ok" if row["ok"] else "FAILED")
        say(f"{name:16s} {state:40s} {row['seconds']:8.1f}s")
    say("chip_smoke summary " + json.dumps(summary, default=str))
    if not args.rehearse:
        print(result_line(all_ok, dev, len(devices)), flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())
