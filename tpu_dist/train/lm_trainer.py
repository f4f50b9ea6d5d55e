"""LMTrainer — the language-model counterpart of `Trainer`.

The reference's training loop is image classification (train_dist.py:
103-127); `Trainer` reproduces it.  The LM family needs the same
conveniences with different plumbing — token batches, next-token loss,
perplexity instead of accuracy — so this is a sibling, built from the
same parts: `parallel.make_partitioned_train_step` (the engine's one
GSPMD step for dp/zero1/fsdp/tp rule sets, with accumulation and the
optional compressed gradient wire; the model-sharded sequence/pipeline/
moe modes ride `parallel.make_spmd_train_step`), the optimizer library
(clipping/EMA/optax all compose), and `train.checkpoint` (async
per-epoch writes).

Determinism contract matches the reference (SURVEY.md §2c.6): seeded
init, seeded per-epoch shuffles identical on every host, replicas
bit-identical.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

import jax
import jax.numpy as jnp

from tpu_dist import parallel
from tpu_dist.models.transformer_lm import lm_loss, lm_perplexity
from tpu_dist.observe import compile_spans, spans
from tpu_dist.train.optim import Optimizer, adamw, clip_by_global_norm


@dataclass
class LMTrainConfig:
    epochs: int = 3
    global_batch: int = 64
    lr: float = 3e-3
    seed: int = 1234
    accum_steps: int = 1
    compute_dtype: str | None = None  # e.g. "bfloat16"
    # ZeRO-3: params/grads/opt state sharded 1/n — routed through the
    # partition engine (the 'fsdp' rule set bound to this mesh's 'data'
    # axis; the legacy shard_map builder is retired).  Checkpoints use
    # the sharded directory format with partition provenance; val
    # perplexity / generate gather params as needed.  Composes with
    # accum_steps and tensor_parallel (engine fsdp×tp rules).
    fsdp: bool = False
    # ZeRO-1: params replicated, optimizer state sharded 1/n — the
    # engine's 'zero1:dp' rule set.  Mutually exclusive with fsdp; same
    # sharded checkpoint format; composes with accum_steps and
    # tensor_parallel (like fsdp).
    zero1: bool = False
    # Tensor parallelism over a 2-D (data x model) mesh: "psum" = the
    # classic Megatron layout (replicated activations, two psums per
    # block, vocab-parallel head — loss_tensor_parallel); "sp" = the
    # Megatron-SP collective-matmul layout (activations sequence-sharded
    # between sublayers, all-gathers/reduce-scatters folded into the
    # matmuls — loss_tensor_parallel_sp).  Params stay replicated either
    # way (row-sharded when composed with fsdp), so checkpoints/eval/
    # generate are unchanged.
    tensor_parallel: str | None = None
    model_axis: str = "model"
    # Sequence/context-parallel TRAINING over a (data x seq) mesh:
    # "ring" = ring attention (K/V blocks rotate via ppermute while each
    # rank holds its sequence shard), "ulysses" = all-to-all head
    # resharding.  Tokens arrive (B/dp, S/seq); the boundary-correct
    # `lm_loss_seq_parallel` makes the seq-axis pmean equal the dense
    # loss.  Params replicated.  Mutually exclusive with the other
    # model-sharding modes.
    sequence_parallel: str | None = None
    seq_axis: str = "seq"
    # Pipeline-parallel training over a (data x pipe) mesh: "gpipe" =
    # the GPipe microbatch schedule (forward-only scheduling, autodiff
    # replays the scan — O(M) activation residuals), "1f1b" = the TRUE
    # 1F1B schedule-driven engine (`parallel.pipeline_engine_loss`):
    # backward ticks interleave with forward ticks, the activation
    # stash is O(n·v) with `pipe_interleave` virtual-stage chunks per
    # rank, and the measured schedule bubble fraction is reported per
    # step through telemetry.  Blocks are staged over the pipe axis
    # inside the compiled step (`TransformerLM.loss_pipeline`, grads
    # psum'd over 'pipe'); params replicated, so checkpoints/eval/
    # generate are unchanged.  Mutually exclusive with the other
    # model-sharding modes.
    pipeline: str | None = None
    pipe_axis: str = "pipe"
    pipe_microbatches: int = 4
    pipe_interleave: int = 2
    # Expert-parallel MoE training: the model must be built with
    # ``moe_experts == data-axis size`` (one expert per rank); the batch
    # shards over 'data' as usual and every MoE layer all_to_all-dispatches
    # tokens to their routed experts (`TransformerLM.loss_moe_ep`, with
    # the balance-loss regularizer).  The gradient contract is the
    # uniform data-axis pmean the step already applies; composes with
    # accum_steps.  NOT combinable with fsdp/zero1 anymore (those route
    # through the engine, and expert dispatch is not a rule vocabulary
    # yet); mutually exclusive with the other model-sharding modes.
    moe: bool = False
    # Bucketed error-feedback compressed gradient sync, INSIDE the
    # partition engine's GSPMD step (comm.compress): a wire spec like
    # 'int8' / 'fp8' / 'float8_e5m2' / 'bf16'.  Works on every
    # engine-routed config — dp, fsdp, zero1, composed mesh_axes
    # (dp×fsdp, dp×tp: model-sharded grads compress at their shard
    # shape over the data axes).  The EF residual rides the
    # optimizer-state checkpoint.  None = exact f32 sync.  Refused by
    # the shard_map-only modes
    # (sequence/pipeline/moe, and the tensor_parallel flag without
    # fsdp/zero1 — use mesh_axes 'dp=A,tp=B' instead).
    grad_compress: str | None = None
    # Global-norm gradient clipping (LM-training staple).  Wraps the
    # optimizer in `train.clip_by_global_norm`, whose shard_update psums
    # squared shard norms — so clipping is by the TRUE global norm under
    # fsdp/zero1 too, and every mode's trajectory still matches dense.
    grad_clip: float | None = None
    # NaN guard (resilience.nan_guard): non-finite loss/grad steps are
    # skipped in-compile (params/opt state unchanged), counted
    # (LMEpochStats.bad_steps), and training continues.  loss_scale arms
    # the dynamic bf16 loss scale (escalating backoff on overflow) —
    # replicated modes only; under fsdp/zero1 the guard is
    # skip-and-count without scaling.
    nan_guard: bool = False
    loss_scale: float | None = None
    # Step-pipeline depth (see train.pipeline_driver): up to this many
    # dispatched-but-unread steps in flight; 0 = synchronous loop.
    # Drained at every observable boundary, so epoch stats / bad_steps /
    # checkpoints are depth-invariant.
    inflight_steps: int = 2
    # Partition engine (parallel.partition): a mesh-axes spec like
    # "dp=8", "zero1:dp=8", "dp=2,fsdp=4", or "dp=2,tp=2" selects a
    # rule set (regex path -> PartitionSpec, Megatron tp vocabulary for
    # the transformer layers) and routes training through ONE GSPMD
    # step: params/opt state sharded per the rules, the weight update
    # sharded over the data axes, composed 2-D/3-D meshes from one
    # knob.  The mesh must carry exactly these axes
    # (partition.build_mesh).  Mutually exclusive with every strategy
    # flag (fsdp/zero1/tensor/sequence/pipeline/moe); grad_compress
    # composes (the quantized wire rides inside the engine step).
    mesh_axes: str | None = None
    # Per-model overrides for the engine: (regex, spec) pairs matched
    # ahead of the built-ins.
    partition_rules: list | None = None
    log: Callable[[str], None] = print


@dataclass
class LMEpochStats:
    epoch: int
    mean_loss: float
    seconds: float
    tokens_per_sec: float
    val_loss: float | None = None
    val_perplexity: float | None = None
    # cumulative non-finite steps skipped by the NaN guard (None = guard off)
    bad_steps: int | None = None


class LMTrainer:
    """Data-parallel LM training over ``(N, S)`` token windows."""

    def __init__(
        self,
        lm,
        mesh,
        config: LMTrainConfig | None = None,
        *,
        optimizer: Optimizer | None = None,
    ):
        compile_spans.install()
        # what building a trainer costs the host, kept past the ring's
        # wrap: the weights' draw (`model.init`) and the state's placement
        # (`trainer.place_state`) are children, the compile stages theirs
        with spans.span("trainer.init", keep=True):
            self._init(lm, mesh, config, optimizer)

    def _init(self, lm, mesh, config, optimizer) -> None:
        self.lm = lm
        self.mesh = mesh
        self.config = config or LMTrainConfig()
        self.world = int(np.prod(mesh.devices.shape))
        self.optimizer = optimizer or adamw(self.config.lr)
        if self.config.grad_clip is not None:
            self.optimizer = clip_by_global_norm(
                self.optimizer, self.config.grad_clip
            )

        # Compressed gradient sync: parsed (and VALIDATED — a typo'd
        # wire dtype fails here, not at trace time) from the config.
        # The wire itself lives INSIDE the partition engine
        # (`make_partitioned_train_step(compress=)`).
        from tpu_dist.comm import compress as compress_mod

        self._compress = compress_mod.parse(self.config.grad_compress)
        self._wrap_ef = (
            self._compress is not None and self._compress.error_feedback
        )
        if self.config.fsdp and self.config.zero1:
            raise ValueError("fsdp and zero1 are mutually exclusive")
        tp = self.config.tensor_parallel
        sp = self.config.sequence_parallel
        pp = self.config.pipeline
        moe = self.config.moe
        if sum(x is not None for x in (tp, sp, pp)) + bool(moe) > 1:
            raise ValueError(
                "tensor_parallel, sequence_parallel, pipeline, and moe "
                "are mutually exclusive trainer modes"
            )
        if tp is not None and tp not in ("psum", "sp"):
            raise ValueError(
                f"tensor_parallel must be 'psum' or 'sp', got {tp!r}"
            )
        if (
            tp is not None
            and self.config.mesh_axes is None
            and self.config.model_axis not in mesh.axis_names
        ):
            raise ValueError(
                f"tensor_parallel needs a {self.config.model_axis!r} "
                f"mesh axis; mesh has {mesh.axis_names}"
            )
        # Partition-engine routing: mesh_axes explicitly, or the legacy
        # fsdp/zero1/dp flags (± tensor_parallel) bound onto this mesh's
        # own axis names — ONE GSPMD step, one rule language (ROADMAP
        # item 2(d)).  The model-sharded LM modes that are not yet a
        # rule vocabulary (sequence/pipeline/moe, and tensor_parallel on
        # replicated params) keep the explicit shard_map step.
        self._ruleset = None
        self._partition_meta = None
        engine_spec, engine_bind = None, None
        if self.config.mesh_axes is not None:
            if self.config.fsdp or self.config.zero1:
                raise ValueError(
                    "mesh_axes selects a partition rule set — it replaces "
                    "the fsdp/zero1 strategy flags, do not combine them"
                )
            if tp is not None or sp is not None or pp is not None or moe:
                raise ValueError(
                    "mesh_axes is a rule-set mode of its own — tensor/"
                    "sequence/pipeline/moe flags select the explicit "
                    "shard_map step instead; express tp composition as a "
                    "'tp' axis in mesh_axes (e.g. 'dp=2,tp=2')"
                )
            if self.config.loss_scale is not None:
                raise ValueError(
                    "loss_scale is not threaded through the partitioned "
                    "step — use nan_guard without loss_scale under "
                    "mesh_axes"
                )
            engine_spec = self.config.mesh_axes
        elif self.config.fsdp or self.config.zero1:
            which = "fsdp" if self.config.fsdp else "zero1"
            if sp is not None:
                raise ValueError(
                    "sequence_parallel is not combinable with fsdp/zero1 "
                    "in the trainer (compose via "
                    "parallel.make_spmd_train_step's batch_spec instead)"
                )
            if pp is not None:
                raise ValueError(
                    "pipeline is not combinable with fsdp/zero1 in the "
                    "trainer (stage params already partition the model)"
                )
            if moe:
                raise ValueError(
                    f"moe is not combinable with {which} anymore: "
                    "fsdp/zero1 route through the partition engine, and "
                    "the expert all_to_all dispatch is not a rule "
                    "vocabulary yet — drop moe or the sharding flag"
                )
            if self.config.loss_scale is not None:
                raise ValueError(
                    "loss_scale is not threaded through the fsdp/zero1 "
                    "engine step — use nan_guard without loss_scale "
                    "there (skip-and-count still applies)"
                )
            data_ax = parallel.DATA_AXIS
            if data_ax not in mesh.axis_names:
                raise ValueError(
                    f"{which} expects a {data_ax!r} mesh axis; mesh has "
                    f"{tuple(mesh.axis_names)} — use mesh_axes to name "
                    "axes explicitly"
                )
            if tp is None and len(mesh.axis_names) != 1:
                raise ValueError(
                    f"{which} without tensor_parallel expects a 1-D "
                    f"{data_ax!r} mesh (got {tuple(mesh.axis_names)}); "
                    "use mesh_axes for composed meshes"
                )
            # fsdp/zero1 × tensor_parallel: the engine's tp rule
            # vocabulary takes over (both the 'psum' and 'sp' layouts
            # are GSPMD's call now — same global math).
            engine_spec, engine_bind = parallel.strategy_engine_spec(
                mesh, fsdp=self.config.fsdp, zero1=self.config.zero1,
                data_axis=data_ax,
                tp_axis=self.config.model_axis if tp is not None else None,
            )
        elif (
            tp is None and sp is None and pp is None and not moe
            and self.config.loss_scale is None
            and tuple(mesh.axis_names) == (parallel.DATA_AXIS,)
        ):
            # plain dp on the standard 1-D mesh → engine
            engine_spec, engine_bind = parallel.strategy_engine_spec(
                mesh, data_axis=parallel.DATA_AXIS
            )
        self._engine_mode = engine_spec is not None
        self._sharded_mode = (
            self.config.fsdp or self.config.zero1
            or self.config.mesh_axes is not None
        )
        # Compressed training checkpoints via the SHARDED directory
        # format too: the error-feedback residual is per-rank (sharded
        # over the data axes), which the single-writer npz cannot hold
        # on a multi-process mesh.
        self._sharded_ckpt = self._sharded_mode or self._wrap_ef
        if self._engine_mode:
            self._ruleset, self._partition_meta = (
                parallel.resolve_trainer_rules(
                    "LMTrainer", mesh, engine_spec,
                    user_rules=self.config.partition_rules,
                    bind=engine_bind,
                )
            )
        if self.config.loss_scale is not None and not self.config.nan_guard:
            raise ValueError("loss_scale requires nan_guard=True")
        if self.config.nan_guard:
            from tpu_dist.resilience.guards import nan_guard

            # Outermost wrapper (over grad_clip): the step builder reads
            # current_scale from the top-level optimizer, and a NaN grad
            # must be skipped before clipping touches it.  Without
            # loss_scale the guard is skip-and-count ONLY — pin the scale
            # to 1.0 (max_scale clamps growth) so no scaling ever arms
            # itself.
            if self.config.loss_scale is None:
                self.optimizer = nan_guard(self.optimizer, max_scale=1.0)
            else:
                self.optimizer = nan_guard(
                    self.optimizer, init_scale=self.config.loss_scale
                )
        if self._compress is not None and not self._engine_mode:
            # The compressed wire IS the engine's now: the model-sharded
            # LM modes that still run the explicit shard_map step cannot
            # carry it.  tensor_parallel could — through the engine —
            # so its refusal points there; sequence/pipeline/moe
            # genuinely lack a compressed path.
            if tp is not None:
                compress_mod.refuse_model_axes(
                    "LMTrainer", [self.config.model_axis],
                    rules=f"tensor_parallel={tp!r}",
                    hint="mesh_axes engine mode (e.g. 'dp=2,tp=2') "
                    "carries the compressed wire over the data axes of "
                    "a tp mesh — use it instead of the tensor_parallel "
                    "flag.",
                )
            if sp is not None or pp is not None or moe:
                mode_axes, mode = [], None
                if sp is not None:
                    mode_axes, mode = (
                        [self.config.seq_axis], f"sequence_parallel={sp!r}"
                    )
                elif pp is not None:
                    mode_axes, mode = (
                        [self.config.pipe_axis], f"pipeline={pp!r}"
                    )
                else:
                    mode = "moe=True (expert all_to_all over the data axis)"
                compress_mod.refuse_model_axes(
                    "LMTrainer", mode_axes, rules=mode,
                    hint="No engine rule vocabulary exists for this mode "
                    "yet (ROADMAP item 2), so there is no compressed "
                    "wire for it either.",
                )
            raise ValueError(
                "LMTrainer: grad_compress rides the partition engine's "
                "quantized wire — this configuration routes through the "
                "explicit shard_map step (loss_scale or a non-'data' "
                "mesh); drop the conflicting option or use mesh_axes "
                "engine mode"
            )
        if moe:
            world_data = mesh.shape.get(parallel.DATA_AXIS)
            if getattr(lm, "moe_experts", 0) != world_data:
                raise ValueError(
                    f"moe mode needs lm.moe_experts == data-axis size "
                    f"({world_data}), got {getattr(lm, 'moe_experts', 0)}"
                )
        if sp is not None:
            if sp not in ("ring", "ulysses"):
                raise ValueError(
                    f"sequence_parallel must be 'ring' or 'ulysses', "
                    f"got {sp!r}"
                )
            if self.config.seq_axis not in mesh.axis_names:
                raise ValueError(
                    f"sequence_parallel needs a {self.config.seq_axis!r} "
                    f"mesh axis; mesh has {mesh.axis_names}"
                )
        self._pipe_schedule = None
        if pp is not None:
            if pp not in ("gpipe", "1f1b"):
                raise ValueError(
                    f"pipeline must be 'gpipe' or '1f1b', got {pp!r}"
                )
            if self.config.pipe_axis not in mesh.axis_names:
                raise ValueError(
                    f"pipeline needs a {self.config.pipe_axis!r} mesh "
                    f"axis; mesh has {mesh.axis_names}"
                )
            from tpu_dist.parallel.pipeline import (
                build_schedule,
                default_schedule_kind,
            )

            n_pipe = int(mesh.shape[self.config.pipe_axis])
            v = self.config.pipe_interleave if pp == "1f1b" else 1
            kind = "gpipe" if pp == "gpipe" else default_schedule_kind(v)
            # Built here for two reasons: a bad (n, M, v) combination
            # fails at CONFIG time (not at trace time), and the table's
            # measured bubble fraction feeds the per-step telemetry.
            # The gpipe trainer path still executes via the scan-replay
            # `apply_pipeline` (kept until engine parity is the default
            # everywhere); its table has the identical tick structure,
            # so the reported bubble is the executed one either way.
            self._pipe_schedule = build_schedule(
                n_pipe, self.config.pipe_microbatches, v, kind
            )
        params, _ = lm.init(jax.random.key(self.config.seed))
        from tpu_dist.utils.debug import assert_no_aliasing

        compute = (
            jnp.dtype(self.config.compute_dtype)
            if self.config.compute_dtype
            else None
        )

        def cast(p):
            if compute is None:
                return p
            with jax.named_scope("cast"):
                return jax.tree.map(
                    lambda a: a.astype(compute)
                    if jnp.issubdtype(a.dtype, jnp.floating)
                    else a,
                    p,
                )

        def mode_loss(p, tokens):
            """The per-rank loss for the active model-sharding mode."""
            if tp == "sp":
                # tokens arrive (B/dp, S/tp): batch AND sequence sharded
                return self.lm.loss_tensor_parallel_sp(
                    cast(p), tokens, self.config.model_axis
                )
            if tp == "psum":
                return self.lm.loss_tensor_parallel(
                    cast(p), tokens, self.config.model_axis
                )
            if sp is not None:
                # tokens arrive (B/dp, S/seq): the boundary-correct loss
                logits = self.lm.apply_seq_parallel(
                    cast(p), tokens, self.config.seq_axis, attention=sp
                )
                from tpu_dist.models.transformer_lm import (
                    lm_loss_seq_parallel,
                )

                return lm_loss_seq_parallel(
                    logits.astype(jnp.float32), tokens, self.config.seq_axis
                )
            if pp is not None:
                # "1f1b" = the schedule-driven engine (true backward
                # interleaving); "gpipe" = the scan-replay path.  The
                # engine re-executes the SAME table the trainer built
                # at config time (kind threaded through, so the
                # telemetry bubble always describes the executed
                # schedule).
                return self.lm.loss_pipeline(
                    cast(p), tokens, self.config.pipe_axis,
                    n_microbatches=self.config.pipe_microbatches,
                    interleave=(
                        self.config.pipe_interleave if pp == "1f1b" else 1
                    ),
                    engine=(pp == "1f1b"),
                    schedule_kind=(
                        self._pipe_schedule.kind if pp == "1f1b" else None
                    ),
                )
            if moe:
                return self.lm.loss_moe_ep(
                    cast(p), tokens, parallel.DATA_AXIS
                )
            logits, _ = self.lm.apply(cast(p), {}, tokens)
            with jax.named_scope("loss"):  # the f32 logits are the loss's
                return lm_loss(logits.astype(jnp.float32), tokens)

        def loss_fn(p, s, batch, key):
            (tokens,) = batch
            return mode_loss(p, tokens), ({}, {})

        from jax.sharding import PartitionSpec as P

        # One source of truth for how token batches shard: over batch
        # AND sequence for the Megatron-SP and sequence-parallel modes,
        # batch only otherwise.  fit()/both step builders all use this.
        self._batch_spec = (
            self._ruleset.batch_spec()
            if self._ruleset is not None
            else P(parallel.DATA_AXIS, self.config.model_axis)
            if tp == "sp"
            else P(parallel.DATA_AXIS, self.config.seq_axis)
            if sp is not None
            else None
        )
        with spans.span("trainer.place_state", keep=True):
            if self._engine_mode:
                # Partition-engine path: the DENSE loss on the global batch;
                # XLA's SPMD partitioner derives the per-device program and
                # collectives from the rule-matched shardings (tp rules give
                # the Megatron layout without a tensor-parallel loss fn).
                def engine_loss(p, batch, key):
                    (tokens,) = batch
                    logits, _ = self.lm.apply(cast(p), {}, tokens)
                    with jax.named_scope("loss"):  # the f32 logits are the loss's
                        return lm_loss(logits.astype(jnp.float32), tokens), {}

                built = parallel.make_partitioned_train_step(
                    engine_loss, self.optimizer, mesh, params, self._ruleset,
                    accum_steps=self.config.accum_steps,
                    compress=self._compress,
                )
                self.params, self.opt_state = built.params, built.opt_state
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
                extra = ()
                if tp is not None:
                    extra = (self.config.model_axis,)
                elif sp is not None:
                    extra = (self.config.seq_axis,)
                self.params = parallel.replicate(params, mesh)
                self.opt_state = parallel.replicate(
                    self.optimizer.init(params), mesh
                )
                assert_no_aliasing(self.params, self.opt_state)
                self.step = parallel.make_spmd_train_step(
                    loss_fn, self.optimizer, mesh,
                    accum_steps=self.config.accum_steps,
                    extra_grad_axes=extra,
                    # pipeline: per-rank grads PARTITION the dense gradient
                    # over stages — sum, don't average
                    grad_psum_axes=(
                        (self.config.pipe_axis,) if pp is not None else ()
                    ),
                    batch_spec=self._batch_spec,
                )
        self._model_state = parallel.replicate({}, mesh)
        # Pipeline-schedule accounting for telemetry (static per step):
        # the measured bubble fraction of the executed table.
        self._pipe_summary = None
        if self._pipe_schedule is not None:
            sched = self._pipe_schedule
            self._pipe_summary = {
                "kind": sched.kind,
                "n": sched.n,
                "microbatches": sched.n_microbatches,
                "chunks": sched.n_chunks,
                "ticks": sched.ticks,
                "bubble_fraction": round(sched.bubble_fraction(), 6),
                "stash_depth": sched.stash_depth,
            }
        # Wire accounting for telemetry (static per step): what the
        # engine's compressed sync ships vs what exact fp32 would.
        self._compress_summary = None
        if self._compress is not None:
            self._compress_summary = self._partition.flat_plan.wire_summary(
                "all_reduce"
            )

    def _full_params(self):
        """Full (logical-shape) parameters for eval/decode — identity for
        the replicated path, a compiled all-gather for rule-sharded
        engine state on multi-process meshes (fully-addressable engine
        shards pass through — jnp reads them directly)."""
        if self._engine_mode:
            return parallel.gather_replicated(self.params, self.mesh)
        return self.params

    def fit(
        self,
        windows,
        *,
        epochs: int | None = None,
        val_windows=None,
        checkpoint_dir: str | None = None,
        start_epoch: int = 0,
    ) -> list[LMEpochStats]:
        """``windows``: ``(N, S)`` int tokens (e.g. stacked
        `data.TextCorpus` windows or `models.synthetic_tokens`)."""
        cfg = self.config
        windows = np.asarray(windows)
        n, s = windows.shape
        gb = cfg.global_batch
        if n < gb:
            raise ValueError(
                f"{n} windows < global batch {gb} — shrink the batch or "
                f"use more data"
            )
        steps_per_epoch = n // gb
        from tpu_dist.train import metrics as metrics_mod
        from tpu_dist.train.checkpoint import AsyncCheckpointer

        writer = AsyncCheckpointer() if checkpoint_dir else None
        # Opt-in telemetry (TPU_DIST_TELEMETRY): manifest + per-step JSONL
        # events, heartbeat, host spans, goodput — see docs/observability.md.
        telemetry = metrics_mod.TrainTelemetry(
            world=self.world, mesh=self.mesh, config=cfg, trainer="LMTrainer",
            partition=self._partition_meta,
        )
        telemetry.set_compress(self._compress_summary)
        telemetry.set_pipeline(self._pipe_summary)
        ok = False
        try:
            history = self._fit_loop(
                cfg, windows, n, s, gb, steps_per_epoch, epochs, start_epoch,
                val_windows, checkpoint_dir, writer, telemetry,
            )
            if writer is not None:
                writer.wait()
            ok = True
            return history
        finally:
            # Always runs — a fit that raises must still flush the span
            # trace and mark this rank's heartbeat (crashed, not silent).
            telemetry.finish(ok=ok)

    def _fit_loop(
        self, cfg, windows, n, s, gb, steps_per_epoch, epochs, start_epoch,
        val_windows, checkpoint_dir, writer, telemetry,
    ) -> list[LMEpochStats]:
        """The epoch/step loop of `fit` (split out so fit can wrap it in
        the telemetry try/finally)."""
        from tpu_dist.comm import compress as compress_mod
        from tpu_dist.data.loader import HostLoader
        from tpu_dist.resilience.preempt import PreemptionGuard
        from tpu_dist.train import checkpoint as ckpt_mod
        from tpu_dist.train import metrics as metrics_mod
        from tpu_dist.train.pipeline_driver import PipelineDriver

        history = []
        # `with`: a fit that raises mid-epoch still drains the ring, so
        # already-dispatched steps keep their readbacks/telemetry.
        with PipelineDriver(telemetry, depth=cfg.inflight_steps) as driver, \
                PreemptionGuard() as preempt:
            for epoch in range(
                start_epoch, epochs if epochs is not None else cfg.epochs
            ):
                rng = np.random.default_rng(cfg.seed + epoch)  # host-identical
                order = rng.permutation(n)
                t0 = time.perf_counter()
                total, steps_done = 0.0, 0

                def host_batches(order=order):
                    for b in range(steps_per_epoch):
                        yield (windows[order[b * gb : (b + 1) * gb]],)

                # Background host loader: the fancy-index window gather +
                # sharded device_put run off the critical path, feeding
                # the in-flight ring.
                with HostLoader(
                    host_batches(), self.mesh, spec=self._batch_spec
                ) as batches:
                    for b in range(steps_per_epoch):
                        with telemetry.spans.span(
                            "data_next", step=telemetry.next_step_id
                        ):
                            batch = next(batches, None)
                        telemetry.sample_memory("data")
                        if batch is None:
                            break
                        key = jax.random.fold_in(
                            jax.random.fold_in(
                                jax.random.key(cfg.seed + 1), epoch
                            ), b
                        )
                        (
                            self.params,
                            self._model_state,
                            self.opt_state,
                            completed,
                        ) = driver.step(
                            self.step,
                            (self.params, self._model_state, self.opt_state,
                             batch, key),
                            epoch=epoch,
                            batch_size=gb,
                            nan_guard=cfg.nan_guard,
                            extra=lambda step_s: {
                                "tokens_per_sec_per_chip": round(
                                    gb * s / step_s / self.world, 3
                                ),
                            },
                        )
                        for c in completed:
                            total += c.loss
                            steps_done += 1
                        if preempt.requested:
                            break
                # Observable boundary: every dispatched step's loss lands
                # in this epoch's mean before eval/checkpoint/preempt
                # touch the state.
                for c in driver.drain():
                    total += c.loss
                    steps_done += 1
                if preempt.requested:
                    telemetry.preempted(
                        signal=preempt.signal_name, epoch=epoch,
                        step=steps_done,
                    )
                    # Step boundary after SIGTERM/SIGINT: one synchronous
                    # checkpoint recording the CURRENT (incomplete) epoch
                    # — restore() hands it back as the resume epoch — then
                    # a clean stop.
                    if checkpoint_dir:
                        if writer is not None:
                            writer.wait()
                        tree = {
                            "params": self.params, "opt_state": self.opt_state
                        }
                        with telemetry.goodput.measure("checkpoint") as ck:
                            if self._sharded_ckpt:
                                path = f"{checkpoint_dir}/lm_ckpt_preempt"
                                ckpt_mod.save_sharded(
                                    path, tree, step=epoch,
                                    partition=self._partition_meta,
                                )
                            else:
                                path = f"{checkpoint_dir}/lm_ckpt_preempt.npz"
                                ckpt_mod.save(path, tree, step=epoch)
                        telemetry.checkpoint_done(
                            path=path, epoch=epoch, seconds=ck.seconds,
                        )
                    cfg.log(
                        f"preemption ({preempt.signal_name}) at epoch "
                        f"{epoch} step {steps_done}: "
                        + (
                            "checkpoint written, stopping"
                            if checkpoint_dir
                            else "no checkpoint_dir, stopping"
                        )
                    )
                    break
                dt = time.perf_counter() - t0
                mean = total / steps_per_epoch
                tps = steps_per_epoch * gb * s / dt
                vloss = vppl = None
                if val_windows is not None:
                    with telemetry.goodput.measure("eval"):
                        host = jax.tree.map(np.asarray, self._full_params())
                        vloss, vppl = lm_perplexity(
                            self.lm, host, np.asarray(val_windows),
                            batch=min(64, len(val_windows)),
                        )
                bad = (
                    metrics_mod.bad_steps(self.opt_state)
                    if cfg.nan_guard
                    else None
                )
                cfg.log(
                    f"epoch {epoch}: loss {mean:.4f}  [{tps:,.0f} tok/s]"
                    + (f"  val loss {vloss:.4f} ppl {vppl:.1f}" if vppl else "")
                    + (f"  bad_steps {bad}" if bad else "")
                )
                history.append(
                    LMEpochStats(epoch, mean, dt, tps, vloss, vppl, bad)
                )
                telemetry.epoch_done(
                    epoch=epoch, mean_loss=mean, seconds=dt,
                    tokens_per_sec=round(tps, 3), val_loss=vloss,
                    val_perplexity=vppl, bad_steps=bad,
                )
                telemetry.compress_done(
                    error=compress_mod.ef_error(self.opt_state), epoch=epoch
                )
                if checkpoint_dir:
                    tree = {"params": self.params, "opt_state": self.opt_state}
                    with telemetry.goodput.measure("checkpoint") as ck:
                        if self._sharded_ckpt:
                            # sharded format = a DIRECTORY of shard files — no
                            # .npz suffix (ADVICE r2: a dir named .npz misleads)
                            path = f"{checkpoint_dir}/lm_ckpt_{epoch}"
                            writer.save_sharded(
                                path, tree, step=epoch + 1,
                                partition=self._partition_meta,
                            )
                        else:
                            path = f"{checkpoint_dir}/lm_ckpt_{epoch}.npz"
                            writer.save(path, tree, step=epoch + 1)
                    telemetry.checkpoint_done(
                        path=path, epoch=epoch, seconds=ck.seconds,
                    )
        return history

    def restore(self, path) -> int:
        from tpu_dist.comm import compress as compress_mod
        from tpu_dist.train import checkpoint

        like = {"params": self.params, "opt_state": self.opt_state}
        if self._sharded_ckpt:
            if self._ruleset is not None:
                # Engine mode: elastic resume.  Compatible provenance
                # restores directly; a different rule set or topology is
                # redistributed onto this run's shardings in
                # memory-bounded buckets (train.reshard).
                from tpu_dist.train import reshard as reshard_mod

                state, epoch, _ = reshard_mod.restore_or_redistribute(
                    path, like, self._partition_meta,
                    where=f"restore({path})",
                )
            else:
                # Rebuilt under the templates' shardings — replicated
                # leaves come back replicated, fsdp leaves row-sharded.
                state, epoch = checkpoint.restore_fsdp(path, like)
            self.params = state["params"]
            # A different-world-size checkpoint flat-copies fsdp rows
            # validly (zero padding) but would misdirect the dense
            # per-rank residual — zero it instead.
            self.opt_state = compress_mod.reset_resized_residual(
                state["opt_state"], checkpoint.read_meta(path),
                axis_name=parallel.DATA_AXIS,
            )
            return epoch
        state, epoch = checkpoint.restore(path, like)
        self.params = parallel.replicate(state["params"], self.mesh)
        self.opt_state = parallel.replicate(state["opt_state"], self.mesh)
        return epoch

    def generate(self, prompt, steps: int, **kw):
        """Decode with the current parameters (replicated device arrays
        feed the compiled decode directly; FSDP shards are reassembled
        first)."""
        return self.lm.generate(
            self._full_params(), jnp.asarray(np.asarray(prompt)), steps, **kw
        )
