"""Traffic kind ``train-tokens``: `LMTrainer`'s compiled step fed by
`HostLoader` with seeded rows, a fixed token batch per step.

Set-up builds ONE trainer, drives it from the seeded weights through its
first ``check_steps`` steps by the window's own call and feed (those are
the steps the reference follows), and hands the same object to the window.
"""

from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from chipbench import correct, device as device_mod, schedule
from chipbench.harness import Outcome, RunContext, log, seed_key


# the device program this kind runs
PROGRAMS = ("train_step",)


def run(ctx: RunContext) -> Outcome:
    import jax
    import jax.numpy as jnp

    from tpu_dist import comm, parallel, train
    from tpu_dist.data.loader import HostLoader
    from tpu_dist.utils.platform import host_sync

    cell, rec = ctx.cell, ctx.rec
    fam, model, tc, tr = cell.family, cell.config, cell.config["train"], cell.traffic
    seq = int(tr["seq_len"])
    rows = int(tc["batch_tokens"]) // seq
    micro = int(tc["micro_batch_rows"])
    check_steps = int(tr.get("check_steps", 2))
    devs = ctx.devices[: cell.chips]

    base_key = seed_key(ctx.seed)
    lm = fam.make_lm(model, base_key, "float32", remat=bool(tc.get("remat", False)))
    mesh_axes = tc.get("mesh_axes")
    mesh = (
        parallel.build_mesh(mesh_axes, mesh_devices=devs) if mesh_axes
        else comm.make_mesh(1, ("data",), mesh_devices=devs[:1])
    )
    trainer = train.LMTrainer(
        lm, mesh,
        train.LMTrainConfig(
            global_batch=rows, accum_steps=rows // micro,
            compute_dtype=tc["compute_dtype"], lr=float(tc["lr"]),
            seed=ctx.seed & 0x7FFFFFFF, mesh_axes=mesh_axes, log=log,
        ),
    )
    pool = schedule.token_rows(tr, ctx.seed, rows * int(tr["pool_steps"]), fam.vocab_size(model))
    feed = ((pool[i: i + rows],) for i in itertools.cycle(range(0, len(pool), rows)))
    losses: list[float] = []

    def one_step(loader) -> None:
        with rec.span("data_wait"):
            batch = next(loader)
        with rec.span("train_step"):
            key = jax.random.fold_in(base_key, len(losses))
            (trainer.params, trainer._model_state, trainer.opt_state,
             loss, _) = trainer.step(
                trainer.params, trainer._model_state, trainer.opt_state, batch, key)
            losses.append(host_sync(loss))

    to_ref = jax.jit(lambda t: fam.reference.leaf_norms(fam.to_reference(t)))
    init_prog = fam.make_init(model, "float32", layout="program")
    p_sh = jax.tree.map(lambda a: a.sharding, trainer.params)
    delta = jax.jit(
        lambda p, k: fam.reference.leaf_norms(fam.to_reference(
            jax.tree.map(jnp.subtract, p,
                         jax.lax.with_sharding_constraint(init_prog(k), p_sh))))
    )
    program = {}
    with HostLoader(feed, mesh, spec=trainer._batch_spec) as loader:
        for i in range(check_steps):
            one_step(loader)
            if i == 0:
                # Adam's first moment after one step is (1 - b1) times the
                # gradient the optimizer was given
                m = _opt_moment(trainer.opt_state)
                program["grad_norms"] = {
                    k: np.asarray(v) / (1.0 - 0.9) for k, v in to_ref(m).items()
                }
        program["losses"] = list(losses)
        program["delta_norms"] = jax.tree.map(
            np.asarray, delta(trainer.params, base_key))
        one_step(loader)  # one more, so that nothing of the checks' programs is pending
        setup_s = time.perf_counter() - ctx.t0

        compiles0, n0 = ctx.compiles.value, len(losses)
        start = now = time.perf_counter()
        while now - start < ctx.seconds:
            if ctx.tracer:
                ctx.tracer.maybe_start(now, start, ctx.seconds)
            one_step(loader)
            now = time.perf_counter()
        if ctx.tracer:
            ctx.tracer.stop(now)
        elapsed = now - start
        compiles = ctx.compiles.value - compiles0
    steps = len(losses) - n0
    tokens_per_s = steps * rows * seq / elapsed
    log(f"set-up {setup_s:.1f} s; window: {steps} steps of {rows}x{seq} in {elapsed:.3f} s, loss "
        f"{losses[n0]:.4f} -> {losses[-1]:.4f}, {compiles} compilations")

    slow = sorted(rec.named("train_step", since=start), key=lambda s: -s.ms)[:3]
    log("slowest steps: " + ", ".join(f"{s.ms:.0f} ms at {s.start - start:.1f} s" for s in slow))
    facts = {
        "train_step_ms": [s.ms for s in rec.named("train_step", since=start)],
        "data_wait_ms": [s.ms for s in rec.named("data_wait", since=start)],
        "train_tokens_per_s": tokens_per_s,
        "seq_len": seq,
        "state_bytes_per_chip": parallel.per_device_bytes(trainer.params)
        + parallel.per_device_bytes(trainer.opt_state),
        "compiles_in_window": compiles,
        "hbm_peak_bytes": device_mod.memory_peak_bytes(devs),
    }
    finite = bool(np.all(np.isfinite(losses)))
    # the reference runs once the program's state is freed
    batches = [pool[i * rows: (i + 1) * rows] for i in range(check_steps)]
    del trainer, loader, to_ref, delta, p_sh
    gc.collect()  # the trainer's step closes over it: a cycle
    verdict = correct.check_training(
        fam, model, ctx.seed, batches, program, lr=float(tc["lr"]),
        block_rows=int(tc["reference_block_rows"]), devices=devs,
        limits=cell.config["limits"]["train"], control=ctx.control,
    )
    facts["verdict"] = verdict
    return Outcome(
        end_to_end={"train_tokens_per_s": tokens_per_s, "setup_s": setup_s},
        attempted=steps, failed=0 if finite else steps, correct=verdict.ok and finite,
        facts=facts,
    )


def _opt_moment(opt_state):
    """Adam's ``m`` in the trainer's optimizer state (the engine may wrap
    the optimizer's own state under ``opt``)."""
    st = opt_state
    while "m" not in st:
        st = st["opt"]
    return st["m"]

