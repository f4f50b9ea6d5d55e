"""Data parallelism — the reference's centerpiece, compiled the TPU way.

The reference implements DistributedDataParallel by hand
(tuto.md:204-321): replicate the model, shard the data, and after every
backward pass call ``all_reduce`` *per parameter* then divide by world size
(``average_gradients``, train_dist.py:94-100).  That per-tensor blocking
loop is the didactic gap the tutorial itself points out vs real DDP
(tuto.md:319-320: no bucketing, no compute/comm overlap).

Under XLA the whole train step — forward, backward, gradient averaging,
optimizer update — is one compiled SPMD program, so the collective is
fused, bucketed, and overlapped with the backward pass by the compiler.
Two styles are provided:

- `average_gradients(grads, axis_name)`: the explicit `pmean` over the
  gradient pytree — the literal ``average_gradients`` analog, used inside
  a ``shard_map``'d step.
- `make_train_step(...)`: builds the full jitted step over a mesh: batch
  sharded on the ``data`` axis, params/opt-state replicated, gradients
  averaged, update applied — the whole of train_dist.py:115-124 as one
  XLA program per step.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist.ops import partitioning

DATA_AXIS = "data"


def average_gradients(
    grads: Any, axis_name: str = DATA_AXIS, *, backend: str = "psum"
) -> Any:
    """``average_gradients(model)`` (train_dist.py:94-100) over a pytree:
    sum across data-parallel ranks, divide by world size — i.e. ``pmean``.
    One fused collective over the whole tree instead of one blocking
    all_reduce per parameter (and without the reference's type-guard bug,
    SURVEY.md §2c.2).

    Per-tensor observable behavior (SURVEY.md §7 hard part (b)): the tree
    map issues one collective PER PARAMETER — exactly the reference's
    loop structure — and XLA's combiner then buckets/fuses them; the
    per-tensor semantics are preserved at the program level while the
    schedule gets the fusion the reference lacks (tuto.md:319-320).

    ``backend='ring'`` swaps in the hand-rolled chunked ppermute ring
    (`tpu_dist.parallel.ring_all_reduce_chunked`) — the reference's
    allreduce.py path used for its real purpose.  Numerically equivalent
    (tests assert identical training).  ``backend='int8'`` / ``'fp8'`` /
    ``'bf16'`` use the per-leaf quantized collective
    (`comm.all_reduce_quantized`, 4× / 4× / 2× less ICI traffic, lossy —
    gradient-noise-level error; fp8 = e4m3 wire, relative precision for
    heavy-tailed gradients; bf16 = scale-free cast).  ``'psum'`` (XLA
    AllReduce) is the production default; for the bucketed
    error-feedback engine see ``compress`` on
    `partition.make_partitioned_train_step` (`comm.compress`).
    """
    if backend == "psum":
        return lax.pmean(grads, axis_name)
    n = lax.axis_size(axis_name)
    if backend == "ring":
        from tpu_dist.parallel.ring import ring_all_reduce_chunked

        return jax.tree.map(
            lambda g: ring_all_reduce_chunked(g, axis_name) / n, grads
        )
    if backend in ("int8", "fp8", "bf16"):
        from tpu_dist.comm.collectives import all_reduce_quantized

        # _wire_spec canonicalizes the short spellings (WIRE_ALIASES)
        return jax.tree.map(
            lambda g: all_reduce_quantized(g, axis_name, dtype=backend) / n,
            grads,
        )
    raise ValueError(f"unknown grad-reduce backend {backend!r}")


def make_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    donate: bool = True,
):
    """Build the compiled data-parallel train step.

    Args:
      loss_fn: ``loss_fn(params, batch, key) -> (loss, aux)`` computed on
        the *local* shard of the batch.  ``aux`` is any pytree (e.g. new
        model state, metrics).
      optimizer: a `tpu_dist.train.optim.Optimizer` (init/update pair).
      mesh: mesh whose ``axis_name`` axis shards the batch.
      donate: donate params/opt-state buffers (in-place update on device).

    Returns ``step(params, opt_state, batch, key) -> (params, opt_state,
    loss, aux)`` where ``batch`` arrays are sharded on their leading axis
    over ``axis_name`` and everything else is replicated.  The gradient
    ``pmean`` — the whole of ``average_gradients`` — is inside the compiled
    program, so XLA overlaps it with the backward pass (the fused design
    required for the 8-chip scaling target, SURVEY.md §7 hard part (e)).

    Implemented as the stateless special case of `make_spmd_train_step`.
    """

    def stateful_loss(params, _state, batch, key):
        loss, aux = loss_fn(params, batch, key)
        return loss, ((), aux)

    stateful = make_spmd_train_step(
        stateful_loss, optimizer, mesh, axis_name=axis_name, donate=donate
    )

    def step(params, opt_state, batch, key):
        params, _, opt_state, loss, aux = stateful(
            params, (), opt_state, batch, key
        )
        return params, opt_state, loss, aux

    return step


def _pmean_float_leaves(tree: Any, axis_name: str) -> Any:
    """pmean floating leaves; pass through non-float leaves (which must be
    rank-invariant)."""
    return jax.tree.map(
        lambda a: lax.pmean(a, axis_name)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else a,
        tree,
    )


def accumulate_microbatches(
    grads_and_metrics, params, model_state, batch, key, accum_steps: int
):
    """The microbatch-accumulation scan shared by every step builder
    (replicated DP here; FSDP/ZeRO-1 in `parallel.fsdp`): split the
    local batch into ``accum_steps`` microbatches along axis 0 and scan
    them with a gradient-sum carry, so only one microbatch's activations
    are ever live.

    ``grads_and_metrics(params, state, micro_batch, key) -> (grads,
    loss, new_state, aux)``.  Returns ``(mean_grads, mean_loss,
    final_state, aux)`` — aux float leaves averaged over microbatches,
    non-float leaves from the last microbatch (the step contract).
    The per-microbatch key is ``fold_in(key, i)``.
    """

    def to_micro(a):
        if a.shape[0] % accum_steps:
            raise ValueError(
                f"local batch {a.shape[0]} not divisible by "
                f"accum_steps {accum_steps}"
            )
        return a.reshape(
            (accum_steps, a.shape[0] // accum_steps) + a.shape[1:]
        )

    micro = jax.tree.map(to_micro, batch)
    g0 = jax.tree.map(jnp.zeros_like, params)

    def body(carry, xs):
        state, gacc, lacc = carry
        mb, i = xs
        g, loss, state, aux = grads_and_metrics(
            params, state, mb, jax.random.fold_in(key, i)
        )
        return (state, jax.tree.map(jnp.add, gacc, g), lacc + loss), aux

    with jax.named_scope("grad_accum"):
        (new_state, gsum, lsum), auxs = lax.scan(
            body, (model_state, g0, 0.0), (micro, jnp.arange(accum_steps))
        )
        grads = jax.tree.map(lambda g: g / accum_steps, gsum)
    aux = jax.tree.map(
        lambda a: a.mean(0)
        if jnp.issubdtype(a.dtype, jnp.floating)
        else a[-1],
        auxs,
    )
    return grads, lsum / accum_steps, new_state, aux


def make_spmd_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    donate: bool = True,
    grad_reduce: str = "psum",
    accum_steps: int = 1,
    extra_grad_axes: tuple[str, ...] = (),
    grad_psum_axes: tuple[str, ...] = (),
    batch_spec=None,
):
    """Like `make_train_step` but threads non-differentiated model state
    (e.g. batch-norm running statistics) through the step.

    ``extra_grad_axes``: additional mesh axes to pmean gradients (and
    loss/state/aux) over — the tensor-parallel gradient contract: a
    model-sharded loss's per-rank grad is its shard's contribution, and
    the model-axis mean recovers the dense gradient (tested for both TP
    layouts).  ``grad_psum_axes``: axes whose per-rank grads PARTITION
    the dense gradient and must therefore SUM — the pipeline-parallel
    contract (`TransformerLM.loss_pipeline`: each rank's grads are
    nonzero only on its stage's blocks; loss and aux still pmean, being
    replicated).  ``batch_spec``: PartitionSpec for the batch (default
    ``P(axis_name)``) — e.g. ``P('data', 'model')`` shards token windows
    over batch AND sequence for the Megatron-SP layout.

    ``loss_fn(params, model_state, batch, key) -> (loss, (new_state, aux))``.
    Returns ``step(params, model_state, opt_state, batch, key) ->
    (params, model_state, opt_state, loss, aux)``.  New state's floating
    leaves are cross-replica averaged (SyncBN-style statistics), keeping
    replicas bit-identical — the reference's cross-rank identity invariant
    (SURVEY.md §2c.6) extended to stateful models.

    ``accum_steps=k`` enables gradient accumulation: each rank's batch
    shard is split into ``k`` microbatches processed by a ``lax.scan``
    whose carry accumulates the gradient sum — so only ONE microbatch's
    activations are ever live (HBM scales with ``local_batch / k``), the
    optimizer still sees the mean gradient over the full global batch,
    and the collective still fires once per step.  Stateless models match
    the unaccumulated step to fp tolerance (tests); model state threads
    through microbatches sequentially (its per-microbatch semantics —
    e.g. BN statistics see smaller batches — are inherent to
    accumulation).  Aux float leaves are averaged over microbatches.

    For the bucketed error-feedback compressed gradient wire, use the
    partition engine: `partition.make_partitioned_train_step`'s
    ``compress`` option carries it inside the GSPMD program.
    """
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")

    # A `resilience.nan_guard`-wrapped optimizer advertises its live
    # dynamic loss scale; the builder threads it through the backward
    # pass (scaled loss in, unscaled grads + reported loss out) so the
    # scale protects the bf16 intermediate gradients it exists for.
    scale_fn = getattr(optimizer, "current_scale", None)
    if scale_fn is not None:
        # Import here, not module-top: guards pulls in tpu_dist.train,
        # which circularly imports this package at tpu_dist-init time.
        from tpu_dist.resilience.guards import _poison

    def grads_and_metrics(params, model_state, batch, key, scale=None):
        """(grads, loss, new_state, aux) for one (micro)batch; ``scale``
        (a traced scalar) multiplies the loss before the backward and is
        divided back out of grads and the reported loss."""
        fn = loss_fn
        if scale is not None:
            def fn(p, s, b, k):
                loss, (new_state, aux) = loss_fn(p, s, b, k)
                return loss * scale, (new_state, aux)
        (loss, (new_state, aux)), grads = jax.value_and_grad(
            fn, has_aux=True
        )(params, model_state, batch, key)
        if scale is not None:
            inv = 1.0 / scale
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
        return grads, loss, new_state, aux

    def train_step(params, model_state, opt_state, batch, key):
        # fold over the DATA axis only: model-axis ranks run the same
        # replicated computation and must share keys (dropout identity)
        key = jax.random.fold_in(key, lax.axis_index(axis_name))
        scale = scale_fn(opt_state) if scale_fn is not None else None
        gm = functools.partial(grads_and_metrics, scale=scale)
        if accum_steps == 1:
            grads, loss, new_state, aux = gm(params, model_state, batch, key)
        else:
            grads, loss, new_state, aux = accumulate_microbatches(
                gm, params, model_state, batch, key, accum_steps
            )
        if scale_fn is not None:
            # Guarded step: a non-finite LOSS must trip the skip even in
            # the corner where every gradient stays finite (e.g. the NaN
            # arises in a branch with zero cotangent) — poison the grads
            # BEFORE the reduce, so the exact psum propagates the NaN to
            # every rank and the guard skips the step.
            grads = _poison(grads, ~jnp.isfinite(loss))
        with jax.named_scope("grad_sync"):
            grads = average_gradients(grads, axis_name, backend=grad_reduce)
            loss = lax.pmean(loss, axis_name)
            for ax in extra_grad_axes:
                grads = jax.tree.map(lambda g: lax.pmean(g, ax), grads)
                loss = lax.pmean(loss, ax)
                new_state = _pmean_float_leaves(new_state, ax)
                aux = _pmean_float_leaves(aux, ax)
            for ax in grad_psum_axes:
                grads = jax.tree.map(lambda g: lax.psum(g, ax), grads)
                loss = lax.pmean(loss, ax)  # replicated loss: mean, not sum
                new_state = _pmean_float_leaves(new_state, ax)
                aux = _pmean_float_leaves(aux, ax)
            new_state = _pmean_float_leaves(new_state, axis_name)
            aux = _pmean_float_leaves(aux, axis_name)
        with jax.named_scope("optimizer"):
            params, new_opt = optimizer.update(params, grads, opt_state)
        return params, new_state, new_opt, loss, aux

    # jit names the program after the function it is handed: `train_step`
    # on the trace's `XLA Modules` line
    mapped = jax.shard_map(
        train_step,
        mesh=mesh,
        in_specs=(
            P(), P(), P(),
            batch_spec if batch_spec is not None else P(axis_name),
            P(),
        ),
        out_specs=(P(), P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(mapped, donate_argnums=(0, 1, 2) if donate else ())


def partitioned_over(
    mesh: Mesh,
    *,
    batch_axes: tuple[str, ...] = (),
    head_axes: tuple[str, ...] = (),
) -> partitioning.Partitioned:
    """The context in which the builder of a `jax.jit` whose arguments
    span ``mesh`` traces what the model computes (made once, entered
    wherever the step is traced).  XLA partitions such a program
    over the mesh, and nothing inside the trace can see that: the
    context mesh says it (`ops.kernel_for_platform` then keeps a Mosaic
    kernel, which cannot be partitioned, out of the program).  Nor can
    the trace see which axes the builder shards the batch over and which
    the heads: with them `nn.dot_product_attention` computes each
    device's share inside a `shard_map`, where the kernel is allowed
    again; without them it stays dense.  A `shard_map` says as much by
    itself; a mesh of one device changes nothing."""
    return partitioning.Partitioned(
        mesh.abstract_mesh, tuple(batch_axes), tuple(head_axes)
    )


def make_train_step_auto(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    donate: bool = True,
):
    """The compiler-driven alternative to `make_spmd_train_step`.

    Instead of writing per-rank SPMD code with an explicit ``pmean``
    (the shard_map style that mirrors the reference's
    ``average_gradients``), this expresses the *global* computation —
    ``loss_fn(params, model_state, global_batch, key)`` over the whole
    batch — under ``jit`` with sharding annotations: batch sharded on
    ``axis_name``, everything else replicated.  XLA's SPMD partitioner
    derives the gradient all-reduce itself (GSPMD), which is the most
    idiomatic modern-JAX form and lets the compiler choose collective
    schedules.  Both styles are tested to produce identical training.

    ``loss_fn`` must compute a mean over the batch axis for gradients to
    match the explicit-pmean path.
    """
    repl = NamedSharding(mesh, P())
    sharded = NamedSharding(mesh, P(axis_name))

    said = partitioned_over(mesh, batch_axes=(axis_name,))

    def train_step(params, model_state, opt_state, batch, key):
        with said:
            (loss, (new_state, aux)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params, model_state, batch, key)
        with jax.named_scope("optimizer"):
            params, opt_state = optimizer.update(params, grads, opt_state)
        return params, new_state, opt_state, loss, aux

    return jax.jit(
        train_step,
        in_shardings=(repl, repl, repl, sharded, repl),
        out_shardings=(repl, repl, repl, repl, repl),
        donate_argnums=(0, 1, 2) if donate else (),
    )


def shard_batch(
    batch: Any, mesh: Mesh, axis_name: str = DATA_AXIS, *, spec=None
) -> Any:
    """Place a host batch on the mesh, sharded over its leading axis —
    the device-side analog of handing each process its partition.
    ``spec`` overrides the default ``P(axis_name)`` (e.g.
    ``P('data', 'model')`` for sequence-sharded token windows)."""
    sharding = NamedSharding(mesh, spec if spec is not None else P(axis_name))
    return jax.tree.map(lambda x: jax.device_put(x, sharding), batch)


def replicate(tree: Any, mesh: Mesh) -> Any:
    """Replicate a pytree (params/opt state) across the mesh — the model
    replication half of data parallelism (tuto.md:216)."""
    sharding = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sharding), tree)
