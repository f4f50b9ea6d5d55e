"""The training loop — ``run(rank, size)`` rebuilt as a mesh trainer.

Reference loop (train_dist.py:103-127): seed 1234, partitioned MNIST,
SGD(lr=0.01, momentum=0.5), 10 epochs; per batch: forward → nll_loss →
backward → ``average_gradients`` → step; per epoch: print rank, epoch,
mean loss.  Here the whole per-batch body is ONE compiled SPMD program
over the mesh (forward+backward+pmean+update fused — the overlap XLA needs
for the scaling target), and the loop around it feeds rank-major global
batches from the deterministic partitioner.

Observable parity: per-epoch mean loss, printed once per epoch.  In the
reference every rank prints the same value (same seed ⇒ identical
replicas, train_dist.py:125-127); under single-controller SPMD the
replicas are identical by construction, so one line stands for all ranks
(noted in the line itself).
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from tpu_dist import nn, parallel
from tpu_dist.data.loader import DistributedLoader, HostLoader, prefetch_to_mesh
from tpu_dist.train.optim import Optimizer, sgd
from tpu_dist.train.pipeline_driver import PipelineDriver


@dataclass
class TrainConfig:
    """The reference's hyperparameters as an explicit config
    (SURVEY.md §5 'Config': batch 128, lr 0.01, momentum 0.5, 10 epochs,
    seed 1234 — train_dist.py:85,105,110,113)."""

    epochs: int = 10
    global_batch: int = 128
    lr: float = 0.01
    momentum: float = 0.5
    seed: int = 1234
    log: Callable[[str], None] = print
    # TPU performance knobs (defaults preserve reference-exact numerics):
    # compute_dtype='bfloat16' runs forward/backward matmuls MXU-native
    # with f32 master weights and f32 loss/grad accumulation; remat
    # rematerializes the forward in the backward pass (HBM for FLOPs).
    compute_dtype: str | None = None
    remat: bool = False
    # Gradient accumulation: split each rank's shard into this many
    # microbatches scanned sequentially (activations HBM / accum_steps);
    # optimizer math unchanged (mean gradient over the global batch).
    accum_steps: int = 1
    # FSDP (ZeRO-3): params/grads/optimizer state sharded 1/n over the
    # mesh axis instead of replicated; checkpoints switch to the sharded
    # per-shard-file format.  Routed through the partition engine (the
    # 'fsdp' rule set bound to this mesh's axis) — the legacy shard_map
    # builder is retired; numerics still match replicated DP (the
    # update is elementwise — tested in test_partition.py).
    fsdp: bool = False
    # ZeRO-1: params replicated, optimizer state sharded 1/n (the memory
    # middle point; same wire cost and trajectory as replicated DP).
    # Mutually exclusive with fsdp; same sharded checkpoint format;
    # routed through the engine like fsdp.
    zero1: bool = False
    # Gradient-reduction backend: 'psum' (XLA AllReduce, exact,
    # default), 'ring' (the hand-rolled chunked ppermute ring, exact),
    # 'int8' / 'fp8' (per-leaf quantized, 4x less ICI traffic, lossy at
    # gradient-noise level).  Replicated-DP mode only.
    grad_reduce: str = "psum"
    # Bucketed error-feedback compressed gradient sync, riding INSIDE
    # the partition engine's GSPMD step (comm.compress): a wire spec
    # like 'int8' / 'fp8' / 'float8_e5m2' / 'bf16' (optionally
    # 'int8,bucket_mb=4,block=256').  Works on every engine-routed
    # config — dp, fsdp, zero1, composed mesh_axes; the quantization
    # residual is train-step state that rides the optimizer-state
    # checkpoint — which therefore uses the sharded DIRECTORY format
    # (the residual is per-rank, so a single-writer npz cannot hold it
    # multi-host).  Requires a stateless model, grad_reduce='psum', and
    # no loss_scale (those need the explicit shard_map step, which has
    # no wire).  None = exact f32 sync.
    grad_compress: str | None = None
    # NaN guard (resilience.nan_guard): fused non-finite detection on
    # loss/grads inside the compiled step — a bad step is skipped
    # (params/opt state unchanged), counted (EpochStats.bad_steps), and
    # training continues.  loss_scale arms the dynamic bf16 loss scale
    # (escalating backoff on overflow); replicated-DP mode only.
    nan_guard: bool = False
    loss_scale: float | None = None
    # Step-pipeline depth: up to this many dispatched-but-unread steps
    # in flight (loss/metrics for step N are read back after dispatching
    # step N+K), so the host never stands between two device steps.  0 =
    # the synchronous loop (read back every step immediately).  The
    # driver drains at every observable boundary (epoch end, eval,
    # checkpoint, preemption), so epoch stats, bad_steps, and
    # checkpointed state are bit-identical whatever the depth
    # (tests/test_pipeline_driver.py).
    inflight_steps: int = 2
    # Partition engine (parallel.partition): a mesh-axes spec like
    # "dp=8", "zero1:dp=8", "fsdp=8", or "dp=2,fsdp=4" selects a
    # rule set (regex path -> PartitionSpec) and routes training through
    # ONE GSPMD train step — params/opt-state sharded per the rules, the
    # weight update sharded over the data axes (ZeRO-1 for free), every
    # collective derived by XLA.  The mesh passed to the Trainer must
    # carry exactly these axes (partition.build_mesh builds one).
    # Mutually exclusive with fsdp/zero1/grad_compress/loss_scale;
    # checkpoints use the sharded directory format with partition
    # provenance recorded in the meta (restore validates it).
    mesh_axes: str | None = None
    # Per-model overrides for the engine: list of (regex, spec) pairs
    # matched AHEAD of the built-in rules (spec = PartitionSpec or a
    # string like "None,tp").  Ignored without mesh_axes.
    partition_rules: list | None = None


@dataclass
class EpochStats:
    epoch: int
    mean_loss: float
    seconds: float
    samples_per_sec: float
    eval_accuracy: float | None = None
    # cumulative non-finite steps skipped by the NaN guard (None = guard off)
    bad_steps: int | None = None


class Trainer:
    """Data-parallel trainer for `tpu_dist.nn` models on a 1-D mesh."""

    def __init__(
        self,
        model: nn.Sequential,
        in_shape: tuple[int, ...],
        mesh: Mesh,
        config: TrainConfig | None = None,
        *,
        optimizer: Optimizer | None = None,
        loss: Callable = nn.nll_loss,
    ):
        self.model = model
        self.mesh = mesh
        self.config = config or TrainConfig()
        self.world = int(np.prod(mesh.devices.shape))
        self.optimizer = optimizer or sgd(self.config.lr, self.config.momentum)
        self._loss = loss
        # Compressed gradient sync: parsed (and VALIDATED — a typo'd
        # wire dtype fails here, not at trace time) from the config.
        # The wire itself lives INSIDE the partition engine
        # (`make_partitioned_train_step(compress=)`).
        from tpu_dist.comm import compress as compress_mod

        self._compress = compress_mod.parse(self.config.grad_compress)
        self._wrap_ef = (
            self._compress is not None and self._compress.error_feedback
        )
        if self._compress is not None and self.config.grad_reduce != "psum":
            raise ValueError(
                "grad_compress replaces the gradient reduce — leave "
                f"grad_reduce='psum', not {self.config.grad_reduce!r}"
            )
        if self.config.fsdp and self.config.zero1:
            raise ValueError("fsdp and zero1 are mutually exclusive")
        key = jax.random.key(self.config.seed)
        params, state = model.init(key, in_shape)
        stateless = not jax.tree.leaves(state)
        # Partition-engine routing: mesh_axes explicitly, or the legacy
        # fsdp/zero1/dp flags bound onto this mesh's own axis names —
        # the rule set is resolved (and the mesh validated) at CONFIG
        # time, so a typo'd axis or a mis-shaped mesh fails here, not at
        # trace time.  Plain dp stays on the explicit shard_map builder
        # only when something genuinely needs it: model state (BatchNorm
        # statistics), a non-psum grad_reduce backend, or the dynamic
        # loss scale.
        self._ruleset = None
        self._partition_meta = None
        engine_spec, engine_bind = None, None
        if self.config.mesh_axes is not None:
            if self.config.fsdp or self.config.zero1:
                raise ValueError(
                    "mesh_axes selects a partition rule set — it replaces "
                    "the fsdp/zero1 strategy flags, do not combine them"
                )
            if self.config.grad_reduce != "psum":
                raise ValueError(
                    "mesh_axes routes the gradient sync through the XLA "
                    f"partitioner; grad_reduce={self.config.grad_reduce!r} "
                    "only applies to the explicit shard_map step"
                )
            if self.config.loss_scale is not None:
                raise ValueError(
                    "loss_scale is not threaded through the partitioned "
                    "step — use nan_guard without loss_scale under "
                    "mesh_axes"
                )
            engine_spec = self.config.mesh_axes
        elif self.config.fsdp or self.config.zero1:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    "TrainConfig.fsdp/zero1 expect a 1-D mesh (got axes "
                    f"{tuple(mesh.axis_names)}); express multi-axis "
                    "sharding as a mesh_axes spec instead"
                )
            if self.config.grad_reduce != "psum":
                raise ValueError(
                    "fsdp/zero1 route through the partition engine; "
                    f"grad_reduce={self.config.grad_reduce!r} only "
                    "applies to replicated data-parallel training"
                )
            if self.config.loss_scale is not None:
                raise ValueError(
                    "loss_scale is not threaded through the fsdp/zero1 "
                    "engine step — use nan_guard without loss_scale "
                    "there (skip-and-count still applies)"
                )
            engine_spec, engine_bind = parallel.strategy_engine_spec(
                mesh, fsdp=self.config.fsdp, zero1=self.config.zero1,
                data_axis=str(mesh.axis_names[0]),
            )
        elif (
            stateless
            and len(mesh.axis_names) == 1
            and self.config.grad_reduce == "psum"
            and self.config.loss_scale is None
        ):
            # plain dp, nothing the explicit builder is needed for —
            # one engine, one rule language (ROADMAP item 2(d))
            engine_spec, engine_bind = parallel.strategy_engine_spec(
                mesh, data_axis=str(mesh.axis_names[0])
            )
        if engine_spec is not None:
            self._ruleset, self._partition_meta = (
                parallel.resolve_trainer_rules(
                    "Trainer", mesh, engine_spec,
                    user_rules=self.config.partition_rules,
                    bind=engine_bind,
                )
            )
        elif self._compress is not None:
            raise ValueError(
                "grad_compress rides the partition engine's quantized "
                "wire, which needs a stateless model, grad_reduce='psum', "
                "and no loss_scale — drop the conflicting option or use "
                "mesh_axes engine mode explicitly"
            )
        if self.config.loss_scale is not None and not self.config.nan_guard:
            raise ValueError("loss_scale requires nan_guard=True")
        if self.config.nan_guard:
            from tpu_dist.resilience.guards import nan_guard

            # Outermost wrapper: the step builder reads current_scale
            # from the top-level optimizer.  Without loss_scale the guard
            # is skip-and-count ONLY — pin the scale to 1.0 (max_scale
            # clamps growth) so no scaling ever arms itself.
            if self.config.loss_scale is None:
                self.optimizer = nan_guard(self.optimizer, max_scale=1.0)
            else:
                self.optimizer = nan_guard(
                    self.optimizer, init_scale=self.config.loss_scale
                )

        # (params/state were initialized above — the reference's
        # torch.manual_seed(1234) analog: all replicas share one key.)
        if self._sharded_mode and not stateless:
            raise ValueError(
                "TrainConfig.fsdp/zero1/mesh_axes support stateless models "
                "only (no BatchNorm running stats); use "
                "parallel.make_partitioned_train_step directly for custom "
                "state"
            )
        if self._ruleset is None:
            self.params = parallel.replicate(params, mesh)
            self.model_state = parallel.replicate(state, mesh)
            self.opt_state = parallel.replicate(self.optimizer.init(params), mesh)
            # The step donates all three trees; any buffer shared between
            # them (e.g. an optimizer init that returns params leaves
            # uncopied — device_put maps equal inputs to ONE buffer) would be
            # donated twice and desync/crash the compiled step.  Fail loudly
            # here instead (SURVEY.md §5 donation check).
            from tpu_dist.utils.debug import assert_no_aliasing

            assert_no_aliasing(self.params, self.model_state, self.opt_state)

        compute_dtype = (
            jnp.dtype(self.config.compute_dtype)
            if self.config.compute_dtype
            else None
        )

        def forward(params, model_state, x, key):
            if compute_dtype is not None:
                # bf16 compute, f32 master weights: cast at the boundary;
                # gradients flow back through the cast and land in f32.
                params = jax.tree.map(
                    lambda p: p.astype(compute_dtype)
                    if jnp.issubdtype(p.dtype, jnp.floating)
                    else p,
                    params,
                )
                x = x.astype(compute_dtype)
            scores, new_state = model.apply(
                params, model_state, x, train=True, key=key
            )
            return scores.astype(jnp.float32), new_state

        if self.config.remat:
            forward = jax.checkpoint(forward)

        def loss_fn(params, model_state, batch, key):
            x, y = batch
            scores, new_state = forward(params, model_state, x, key)
            return self._loss(scores, y), (new_state, {})

        if self._ruleset is not None:
            # Partition-engine path: ONE GSPMD step for any rule set —
            # the loss is the GLOBAL computation (mean over the global
            # batch) and XLA derives the per-device program + every
            # collective from the rule-matched shardings; the same
            # 5-tuple wrapper keeps fit() oblivious.  grad_compress
            # rides INSIDE the step as the bucketed quantized wire over
            # the rule set's data axes (`comm.compress`).
            def engine_loss(p, batch, key):
                x, y = batch
                scores, _ = forward(p, state, x, key)
                return self._loss(scores, y), {}

            built = parallel.make_partitioned_train_step(
                engine_loss, self.optimizer, mesh, params, self._ruleset,
                accum_steps=self.config.accum_steps,
                compress=self._compress,
            )
            self.params, self.opt_state = built.params, built.opt_state
            self.model_state = parallel.replicate(state, mesh)
            self._param_template = jax.tree.map(
                lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params
            )
            self._partition = built

            def engine_step(p, ms, os_, batch, key):
                p2, o2, loss, aux = built.step(p, os_, batch, key)
                built.report_attention(self.config.log)  # once traced
                return p2, ms, o2, loss, aux

            self.step = engine_step
        else:
            self.step = parallel.make_spmd_train_step(
                loss_fn, self.optimizer, mesh,
                accum_steps=self.config.accum_steps,
                grad_reduce=self.config.grad_reduce,
            )
        # Wire accounting for telemetry (static per step): what the
        # compressed sync ships vs what exact fp32 would.
        self._compress_summary = None
        if self._compress is not None:
            self._compress_summary = self._partition.flat_plan.wire_summary(
                "all_reduce"
            )

        # batches are sharded over the mesh's leading axis (`evaluate`):
        # a partitioned program
        said = parallel.partitioned_over(mesh, batch_axes=mesh.axis_names[:1])

        def eval_apply(params, state, x):
            with said:
                return model.apply(params, state, x, train=False)[0]

        self._eval_apply = jax.jit(eval_apply)

    @property
    def _sharded_mode(self) -> bool:
        """Single owner of the sharded-vs-replicated format dispatch —
        save/restore/fit must all agree on it.  The partition engine
        (mesh_axes) counts: its params/opt state may live sharded, so
        checkpoints take the per-shard directory format."""
        return (
            self.config.fsdp
            or self.config.zero1
            or self.config.mesh_axes is not None
        )

    @property
    def _sharded_ckpt(self) -> bool:
        """Whether checkpoints use the per-shard-file DIRECTORY format.
        True for fsdp/zero1 state, and ALSO for compressed replicated
        training: the error-feedback residual is per-rank (sharded over
        the data axis), so the single-writer npz — which materializes
        every leaf on process 0 — cannot hold it on a multi-process
        mesh; the sharded writer has each process write its own rows."""
        return self._sharded_mode or self._wrap_ef

    def _ckpt_tree(self) -> dict:
        """The checkpointed state tree (sharded modes drop model_state —
        fsdp/zero1 support stateless models only)."""
        if self._sharded_mode:
            return {"params": self.params, "opt_state": self.opt_state}
        return {
            "params": self.params,
            "model_state": self.model_state,
            "opt_state": self.opt_state,
        }

    def save(self, path, *, epoch: int = 0, async_writer=None) -> None:
        """Checkpoint the full training state (params, model state,
        optimizer) — single writer, replicas identical (SURVEY.md §5).
        With ``async_writer`` (a `checkpoint.AsyncCheckpointer`), the
        file write overlaps subsequent training steps."""
        from tpu_dist.train import checkpoint

        tree = self._ckpt_tree()
        if self._sharded_ckpt:
            # Per-shard files, no global array materialized (``path``
            # becomes a directory — see checkpoint.save_sharded).  The
            # partition-engine trainer records its resolved rule set +
            # mesh axes so restore can validate compatibility.
            if async_writer is not None:
                async_writer.save_sharded(
                    path, tree, step=epoch, partition=self._partition_meta
                )
            else:
                checkpoint.save_sharded(
                    path, tree, step=epoch, partition=self._partition_meta
                )
            return
        if async_writer is not None:
            async_writer.save(path, tree, step=epoch)
        else:
            checkpoint.save(path, tree, step=epoch)

    def restore(self, path) -> int:
        """Restore state saved by `save`; returns the stored epoch index
        (resume point)."""
        from tpu_dist.comm import compress as compress_mod
        from tpu_dist.train import checkpoint

        like = self._ckpt_tree()
        if self._sharded_ckpt:
            if self._ruleset is not None:
                # Engine mode: elastic resume.  Compatible provenance
                # (identical, or a same-rules world resize) restores
                # directly; a different rule set or topology is
                # redistributed onto this run's shardings in
                # memory-bounded buckets (train.reshard).
                from tpu_dist.train import reshard as reshard_mod

                restored, epoch, _ = reshard_mod.restore_or_redistribute(
                    path, like, self._partition_meta,
                    where=f"restore({path})",
                )
            else:
                # Rebuilt under the templates' shardings — replicated
                # leaves come back replicated, fsdp leaves row-sharded.
                restored, epoch = checkpoint.restore_fsdp(path, like)
            self.params = restored["params"]
            # A checkpoint from a DIFFERENT world size flat-copies fsdp
            # rows validly (zero padding) but would misdirect the dense
            # per-rank residual — zero it instead (one step of re-paid
            # quantization error, not garbage feedback).
            self.opt_state = compress_mod.reset_resized_residual(
                restored["opt_state"], checkpoint.read_meta(path),
                axis_name=parallel.DATA_AXIS,
            )
            if not self._sharded_mode:
                self.model_state = restored["model_state"]
            return epoch
        state, epoch = checkpoint.restore(path, like)
        self.params = parallel.replicate(state["params"], self.mesh)
        self.model_state = parallel.replicate(state["model_state"], self.mesh)
        self.opt_state = parallel.replicate(state["opt_state"], self.mesh)
        return epoch

    def fit(
        self,
        dataset,
        *,
        epochs: int | None = None,
        start_epoch: int = 0,
        checkpoint_dir: str | None = None,
        trace_dir: str | None = None,
        eval_dataset=None,
    ) -> list[EpochStats]:
        """Run the training loop.

        ``start_epoch`` resumes mid-schedule (pair with `restore`);
        ``checkpoint_dir`` writes ``ckpt_<epoch>.npz`` after each epoch
        (fsdp/zero1 state uses the sharded DIRECTORY format, named
        ``ckpt_<epoch>`` — no misleading .npz suffix on a directory) —
        asynchronously: the device→host snapshot is taken inline but the
        file write overlaps the next epoch's steps (joined before `fit`
        returns);
        ``trace_dir`` captures a jax.profiler trace of epoch
        ``start_epoch`` (perfetto-viewable — SURVEY.md §5 tracing);
        ``eval_dataset`` reports held-out accuracy after each epoch
        (an extension — the reference prints train loss only).
        """
        from tpu_dist.train import metrics as metrics_mod

        cfg = self.config
        loader = DistributedLoader(
            dataset, self.world, cfg.global_batch, seed=cfg.seed
        )
        if loader.steps_per_epoch == 0:
            raise ValueError(
                f"dataset of {len(dataset)} samples gives each of the "
                f"{self.world} shards fewer than the local batch "
                f"({loader.local_batch}) — zero steps per epoch; shrink the "
                f"batch, the world size, or use more data"
            )
        step_key = jax.random.key(cfg.seed + 1)
        from tpu_dist.train.checkpoint import AsyncCheckpointer

        ckpt_writer = AsyncCheckpointer() if checkpoint_dir is not None else None
        suffix = "" if self._sharded_ckpt else ".npz"
        # Opt-in telemetry (TPU_DIST_TELEMETRY): manifest + per-step JSONL
        # events, heartbeat, host spans, goodput — see docs/observability.md.
        telemetry = metrics_mod.TrainTelemetry(
            world=self.world, mesh=self.mesh, config=cfg, trainer="Trainer",
            partition=self._partition_meta,
        )
        telemetry.set_compress(self._compress_summary)
        ok = False
        try:
            history = self._fit_loop(
                cfg, loader, epochs, start_epoch, checkpoint_dir, trace_dir,
                eval_dataset, step_key, ckpt_writer, suffix, telemetry,
            )
            if ckpt_writer is not None:
                ckpt_writer.wait()
            ok = True
            return history
        finally:
            # Always runs — a fit that raises must still flush the span
            # trace and mark this rank's heartbeat (crashed, not silent).
            telemetry.finish(ok=ok)

    def _fit_loop(
        self, cfg, loader, epochs, start_epoch, checkpoint_dir, trace_dir,
        eval_dataset, step_key, ckpt_writer, suffix, telemetry,
    ) -> list[EpochStats]:
        """The epoch/step loop of `fit` (split out so fit can wrap it in
        the telemetry try/finally)."""
        from tpu_dist.comm import compress as compress_mod
        from tpu_dist.resilience.preempt import PreemptionGuard
        from tpu_dist.train import metrics as metrics_mod

        history = []
        # `with`: a fit that raises mid-epoch still drains the ring, so
        # already-dispatched steps keep their readbacks/telemetry.
        with PipelineDriver(telemetry, depth=cfg.inflight_steps) as driver, \
                PreemptionGuard() as preempt:
            for epoch in range(
                start_epoch, epochs if epochs is not None else cfg.epochs
            ):
                t0 = time.perf_counter()
                total_loss, num_batches = 0.0, 0
                with metrics_mod.trace(trace_dir if epoch == start_epoch else None):
                    # Background host loader: batch assembly + sharded
                    # device_put off the critical path, feeding the ring
                    # (the `with` joins the worker even on an early
                    # preemption break).
                    with HostLoader(
                        loader.epoch(epoch), self.mesh,
                        axis_name=self.mesh.axis_names[0],
                        # engine mode: the batch shards over the rule
                        # set's data axes (e.g. dp AND fsdp)
                        spec=(
                            self._ruleset.batch_spec()
                            if self._ruleset is not None
                            else None
                        ),
                    ) as batches:
                        for bi in range(loader.steps_per_epoch):
                            with telemetry.spans.span(
                                "data_next", step=telemetry.next_step_id
                            ):
                                batch = next(batches, None)
                            telemetry.sample_memory("data")
                            if batch is None:
                                break
                            # fold epoch and batch index separately: no
                            # collisions however many steps an epoch has
                            key = jax.random.fold_in(
                                jax.random.fold_in(step_key, epoch), bi
                            )
                            (
                                self.params,
                                self.model_state,
                                self.opt_state,
                                completed,
                            ) = driver.step(
                                self.step,
                                (self.params, self.model_state,
                                 self.opt_state, batch, key),
                                epoch=epoch,
                                batch_size=cfg.global_batch,
                                nan_guard=cfg.nan_guard,
                            )
                            for c in completed:
                                total_loss += c.loss
                                num_batches += 1
                            if preempt.requested:
                                break
                    # Epoch boundary (also the eval/checkpoint/preempt
                    # boundary): drain the ring so every dispatched step's
                    # loss is in this epoch's mean and the device queue is
                    # empty before any state is observed.
                    for c in driver.drain():
                        total_loss += c.loss
                        num_batches += 1
                if preempt.requested:
                    telemetry.preempted(
                        signal=preempt.signal_name, epoch=epoch,
                        step=num_batches,
                    )
                    # Step boundary after SIGTERM/SIGINT: write one
                    # synchronous checkpoint for the CURRENT (incomplete)
                    # epoch — restore() returns this epoch, so resume
                    # redoes it from its first batch — and stop cleanly.
                    if checkpoint_dir is not None:
                        if ckpt_writer is not None:
                            ckpt_writer.wait()
                        path = f"{checkpoint_dir}/ckpt_preempt{suffix}"
                        with telemetry.goodput.measure("checkpoint") as ck:
                            self.save(path, epoch=epoch)
                        telemetry.checkpoint_done(
                            path=path, epoch=epoch, seconds=ck.seconds,
                        )
                    cfg.log(
                        f"preemption ({preempt.signal_name}) at epoch "
                        f"{epoch} step {num_batches}: "
                        + (
                            "checkpoint written, stopping"
                            if checkpoint_dir is not None
                            else "no checkpoint_dir, stopping"
                        )
                    )
                    break
                dt = time.perf_counter() - t0
                mean_loss = total_loss / max(num_batches, 1)
                sps = num_batches * cfg.global_batch / dt
                # train_dist.py:125-127 observable — one line stands for all
                # (identical) ranks.
                acc = None
                if eval_dataset is not None:
                    with telemetry.goodput.measure("eval"):
                        acc = self.evaluate(eval_dataset)
                bad = (
                    metrics_mod.bad_steps(self.opt_state)
                    if cfg.nan_guard
                    else None
                )
                cfg.log(
                    f"Rank all (x{self.world} identical replicas), epoch {epoch}: "
                    f"{mean_loss:.4f}  [{sps:,.0f} samples/s]"
                    + (f"  eval acc {acc:.4f}" if acc is not None else "")
                    + (f"  bad_steps {bad}" if bad else "")
                )
                history.append(EpochStats(epoch, mean_loss, dt, sps, acc, bad))
                telemetry.epoch_done(
                    epoch=epoch, mean_loss=mean_loss, seconds=dt,
                    samples_per_sec=round(sps, 3), eval_accuracy=acc,
                    bad_steps=bad,
                )
                telemetry.compress_done(
                    error=compress_mod.ef_error(self.opt_state), epoch=epoch
                )
                if checkpoint_dir is not None:
                    path = f"{checkpoint_dir}/ckpt_{epoch}{suffix}"
                    with telemetry.goodput.measure("checkpoint") as ck:
                        self.save(path, epoch=epoch + 1, async_writer=ckpt_writer)
                    telemetry.checkpoint_done(
                        path=path, epoch=epoch, seconds=ck.seconds,
                    )
        return history

    def evaluate(self, dataset, *, batch_size: int = 1024) -> float:
        """Top-1 accuracy with dropout off, data-parallel over the mesh.

        Every sample is scored: the trailing partial batch is zero-padded
        to the compiled batch shape and the padding masked out of the
        count.  Batches are sharded over the mesh's leading axis, so eval
        uses all chips like training does."""
        n = len(dataset)
        if n == 0:
            raise ValueError("cannot evaluate an empty dataset")
        # Round the batch to a multiple of the mesh size (sharding needs
        # equal pieces), never below it.
        batch_size = max(self.world, min(batch_size, n) // self.world * self.world)
        eval_params = self.params
        if self._ruleset is not None:
            # engine mode (incl. the fsdp/zero1 flags): rule-sharded
            # params all-gather once when any shard is non-addressable
            # (identity on one process — jnp reads sharded arrays)
            eval_params = parallel.gather_replicated(self.params, self.mesh)
        # Eval batches ride the same prefetch pipeline as training: the
        # pad/stack assembly and H2D transfer for batch i+1 overlap the
        # compiled apply of batch i (labels stay on the host — only the
        # pixels travel).
        starts = list(range(0, n, batch_size))

        def host_batches():
            for i in starts:
                xs = dataset.images[i : i + batch_size]
                if len(xs) < batch_size:
                    pad = batch_size - len(xs)
                    xs = np.concatenate(
                        [xs, np.zeros((pad,) + xs.shape[1:], xs.dtype)]
                    )
                yield (xs,)

        correct = 0
        prefetched = prefetch_to_mesh(
            host_batches(), self.mesh, axis_name=self.mesh.axis_names[0]
        )
        for i, (xs,) in zip(starts, prefetched):
            ys = dataset.labels[i : i + batch_size]
            scores = self._eval_apply(eval_params, self.model_state, xs)
            pred = np.asarray(scores).argmax(-1)[: len(ys)]
            correct += int((pred == ys).sum())
        return correct / n
