"""Bucketed error-feedback compressed gradient sync (the wire engine).

`collectives.all_reduce_quantized` is a per-leaf collective: every
parameter tensor ships as its own quantized allreduce, with one scale for
the whole chunk and the quantization error thrown away.  This module is
the production form of that idea (EQuARX-style, PAPERS.md arxiv
2506.17615): the gradient pytree is flattened into fixed-size flat
BUCKETS (~4 MB of fp32 payload each, per-block scales inside), each
bucket ships exactly once over a quantized collective — int8,
float8_e4m3, float8_e5m2, or a scale-free bfloat16 wire — and the
quantization error is carried as an explicit ERROR-FEEDBACK residual
that is added back into the next step's gradient, so the compressed
trajectory converges like exact sync instead of accumulating bias.

Layout (`FlatPlan`): every leaf is flattened and zero-padded to ``(n,
k_leaf)`` rows exactly like `parallel.fsdp` stores its shards, the rows
concatenate into one ``(n, K)`` matrix (row r = the data destined to
rank r), and K pads up to a whole number of per-destination bucket
chunks.  That single layout serves BOTH wire patterns:

- ``all_reduce_rows``: per bucket, a quantized reduce-scatter
  (``all_to_all`` of 1-byte chunks + per-block scales, dequantize-sum in
  f32) followed by a quantized all-gather of the re-quantized reduced
  chunk — the bandwidth-optimal allreduce with 1-byte lanes.  Used by
  the replicated-DP step.
- ``reduce_scatter_rows``: the first half only — each rank ends with its
  f32-reduced row, which `FlatPlan.shard_rows` slices back into
  per-leaf ``(1, k)`` rows.  Half the wire cost of the allreduce; kept
  as a manual-sharding primitive (the retired fsdp/zero1 builders'
  gradient hop).

The production consumer is the PARTITION ENGINE:
`parallel.make_partitioned_train_step(compress=...)` runs
`all_reduce_rows` over the rule set's composed data axes inside its
GSPMD program (model-sharded leaves at their shard shape via a nested
shard_map over the model axes), with the EF residual as engine opt
state (`init_engine_ef_state` / `engine_residual_spec`).

Error feedback covers BOTH quantization rounds of the allreduce: the
local error ``acc - dequant(quant(acc))`` is fed back everywhere, and
rank r additionally feeds back the second-round (all-gather leg) error
of its own chunk — which it alone can compute exactly — so the engine's
only systematic loss is one step of delay on the residual.

Non-finite safety: NaN does NOT propagate through an int8 cast the way
it does through an exact psum, so a poisoned gradient could silently
corrupt the residual forever while shipping finite garbage.  Every
compressed sync therefore reduces a global all-finite predicate first
(one scalar psum); on a poisoned step the residual is held unchanged and
the OUTPUT gradients are NaN'd, so a `resilience.nan_guard` optimizer
skips the step exactly as it would under exact sync.

Config parsing (`parse`) rejects unknown wire dtypes at config-parse
time — a typo'd ``grad_compress`` fails at trainer construction, not at
trace time deep inside a compiled step.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from tpu_dist.comm.collectives import WIRE_ALIASES, _wire_spec
from tpu_dist.comm.mesh import DEFAULT_AXIS

_OFF = ("", "off", "none", "0", "false")


@dataclass(frozen=True)
class CompressConfig:
    """How gradients ride the wire.

    ``wire``: canonical wire dtype name (see `WIRE_ALIASES`).
    ``bucket_bytes``: fp32 gradient payload per collective (~4 MB
    default); the engine issues O(total_bytes / bucket_bytes)
    collectives, each a fixed-size flat bucket.
    ``block``: elements per quantization scale inside a bucket (per-block
    scales bound the error to the BLOCK's dynamic range, not the
    tensor's).  Ignored by the scale-free bfloat16 wire.
    ``error_feedback``: carry the quantization error into the next step's
    gradient (on by default — turning it off is for ablations only).
    """

    wire: str = "int8"
    bucket_bytes: int = 4 << 20
    block: int = 256
    error_feedback: bool = True

    def __post_init__(self):
        canon = WIRE_ALIASES.get(str(self.wire).lower())
        if canon is None:
            raise ValueError(
                f"unknown compress wire dtype {self.wire!r}; one of "
                f"{sorted(set(WIRE_ALIASES))}"
            )
        object.__setattr__(self, "wire", canon)
        _wire_spec(canon)  # must exist in the collective wire table
        if self.bucket_bytes < 4:
            raise ValueError(f"bucket_bytes must be >= 4, got {self.bucket_bytes}")
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")

    @property
    def wire_itemsize(self) -> int:
        return jnp.dtype(_wire_spec(self.wire)[0]).itemsize


def parse(spec) -> CompressConfig | None:
    """Parse a compress spec into a `CompressConfig` (or None = off).

    Accepts a `CompressConfig` (validated passthrough), None / "off" /
    "none" / "", a bare wire name (``"int8"``, ``"fp8"``, ``"bf16"``,
    ``"float8_e5m2"``), or a comma-form with knobs:
    ``"int8,bucket_mb=4,block=256,ef=1"``.  Unknown wire dtypes and
    malformed knobs raise HERE — config-parse time, not trace time.
    """
    if spec is None:
        return None
    if isinstance(spec, CompressConfig):
        return spec
    text = str(spec).strip().lower()
    if text in _OFF:
        return None
    parts = [p.strip() for p in text.split(",") if p.strip()]
    kw: dict[str, Any] = {"wire": parts[0]}
    for part in parts[1:]:
        if "=" not in part:
            raise ValueError(
                f"malformed compress option {part!r} in {spec!r} "
                f"(expected key=value)"
            )
        k, v = (s.strip() for s in part.split("=", 1))
        if k in ("bucket_mb",):
            kw["bucket_bytes"] = int(float(v) * (1 << 20))
        elif k == "bucket_bytes":
            kw["bucket_bytes"] = int(v)
        elif k == "block":
            kw["block"] = int(v)
        elif k in ("ef", "error_feedback"):
            if v in ("1", "true", "on", "yes"):
                kw["error_feedback"] = True
            elif v in _OFF or v == "no":
                kw["error_feedback"] = False
            else:  # a typo must not silently flip an ablation switch
                raise ValueError(
                    f"bad compress option {k}={v!r} in {spec!r} "
                    f"(expected on/off)"
                )
        else:
            raise ValueError(f"unknown compress option {k!r} in {spec!r}")
    return CompressConfig(**kw)


def refuse_model_axes(
    where: str,
    axes,
    *,
    rules: str | None = None,
    hint: str | None = None,
) -> None:
    """Raise the model-sharding refusal with its CAUSE attached: the
    compressed wire reduces over the pure data axis only, and a bare
    "not supported" hides which axis (and which mode / partition rule)
    put the gradient on a model-sharded layout.  ``axes`` names the
    offending mesh axes; ``rules`` names the trainer mode or partition
    rule set that produced them."""
    axes = tuple(axes)
    axes_s = (
        f"model-sharded ax{'is' if len(axes) == 1 else 'es'} "
        + ", ".join(repr(a) for a in axes)
        if axes
        else "a model-sharded gradient layout"
    )
    raise ValueError(
        f"{where}: grad_compress compresses the pure data-axis gradient "
        f"sync only; {axes_s}"
        + (f" (produced by {rules})" if rules else "")
        + " cannot ride the quantized wire — drop grad_compress or the "
        "model-sharding axes"
        + (f". {hint}" if hint else "")
    )


# ---------------------------------------------------------------------------
# Flat bucket layout
# ---------------------------------------------------------------------------


class FlatPlan:
    """Static layout of a gradient pytree as one ``(n, K_pad)`` matrix.

    Row r carries the data destined to rank r (the fsdp row convention:
    each leaf flattens and zero-pads to ``(n, k_leaf)``; rows concatenate
    leaf by leaf).  ``K_pad`` rounds K up to a whole number of
    per-destination bucket chunks of ``chunk`` elements, and ``chunk`` is
    a multiple of the scale block, so every bucket quantizes uniformly.
    Built from SHAPES only — usable on tracers and templates alike.
    """

    def __init__(self, template: Any, n: int, cfg: CompressConfig):
        self.n = int(n)
        self.cfg = cfg
        leaves, self.treedef = jax.tree_util.tree_flatten(template)
        self.shapes = [tuple(leaf.shape) for leaf in leaves]
        self.dtypes = [jnp.dtype(leaf.dtype) for leaf in leaves]
        self.ks = [
            -(-max(int(math.prod(s)), 0) // self.n) for s in self.shapes
        ]  # ceil(size / n): the fsdp (n, k) row length per leaf
        self.K = sum(self.ks)
        block = max(1, int(cfg.block))
        # per-destination chunk: bucket_bytes of fp32 payload across the
        # whole (n, chunk) slab, rounded up to whole scale blocks — but
        # never beyond the payload itself (a tiny model must not ship a
        # mostly-padding 4 MB bucket)
        per_dest = max(1, cfg.bucket_bytes // 4 // self.n)
        k_blocks = -(-max(self.K, 1) // block) * block
        self.chunk = min(-(-per_dest // block) * block, k_blocks)
        self.block = block
        self.K_pad = -(-max(self.K, 1) // self.chunk) * self.chunk
        self.n_buckets = self.K_pad // self.chunk

    # --- tree <-> rows ----------------------------------------------------

    def to_rows(self, grads: Any) -> jax.Array:
        """Pytree -> the ``(n, K_pad)`` f32 row matrix."""
        from tpu_dist.utils.tree import pad_to_multiple

        leaves = jax.tree_util.tree_leaves(grads)
        rows = [
            pad_to_multiple(jnp.ravel(g).astype(jnp.float32), self.n).reshape(
                self.n, -1
            )
            for g in leaves
        ]
        out = jnp.concatenate(rows, axis=1) if rows else jnp.zeros((self.n, 0))
        if self.K_pad > self.K:
            out = jnp.pad(out, ((0, 0), (0, self.K_pad - self.K)))
        return out

    def from_rows(self, rows: jax.Array) -> Any:
        """``(n, K_pad)`` row matrix -> pytree (original shapes/dtypes)."""
        leaves, off = [], 0
        for shape, dtype, k in zip(self.shapes, self.dtypes, self.ks):
            size = int(math.prod(shape))
            flat = lax.slice_in_dim(rows, off, off + k, axis=1).reshape(-1)
            leaves.append(flat[:size].reshape(shape).astype(dtype))
            off += k
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def shard_rows(self, local_row: jax.Array) -> Any:
        """One rank's reduced ``(K_pad,)`` row -> the per-leaf ``(1, k)``
        row shards the fsdp/zero1 optimizer update consumes (the exact
        output format of `parallel.fsdp._reduce_scatter_grads`)."""
        shards, off = [], 0
        for k in self.ks:
            shards.append(
                lax.slice_in_dim(local_row, off, off + k, axis=0).reshape(1, k)
            )
            off += k
        return jax.tree_util.tree_unflatten(self.treedef, shards)

    # --- accounting -------------------------------------------------------

    def payload_bytes(self, wire: bool = True) -> int:
        """Per-step quantized payload bytes across the whole (n, K_pad)
        slab (scales included), or the fp32 equivalent (``wire=False``)."""
        total = self.n * self.K_pad
        if not wire:
            return total * 4
        per_elem = self.cfg.wire_itemsize
        scale_bytes = 0
        if self.cfg.wire != "bfloat16":  # f32 scale per block
            scale_bytes = (total // self.block) * 4
        return total * per_elem + scale_bytes

    def bytes_on_wire(self, mode: str = "all_reduce") -> int:
        """Bytes each rank moves per step (ring lower bound: allreduce =
        2(n-1)/n of the payload, reduce-scatter = (n-1)/n)."""
        factor = 2 if mode == "all_reduce" else 1
        return int(factor * (self.n - 1) / max(self.n, 1) * self.payload_bytes())

    def bytes_exact(self, mode: str = "all_reduce") -> int:
        factor = 2 if mode == "all_reduce" else 1
        return int(
            factor * (self.n - 1) / max(self.n, 1) * self.payload_bytes(False)
        )

    def wire_summary(self, mode: str = "all_reduce") -> dict:
        """The telemetry record: what one step costs on the wire."""
        return {
            "wire": self.cfg.wire,
            "mode": mode,
            "buckets": self.n_buckets,
            "bucket_bytes": self.chunk * self.n * 4,
            "bytes_on_wire": self.bytes_on_wire(mode),
            "bytes_exact": self.bytes_exact(mode),
        }

    def analysis_expectations(self) -> dict:
        """What `tpu_dist.analysis` should find in a compiled step that
        syncs through this plan: the wire itemsize every gradient-payload
        collective must carry, and the widest operand of a WIDER dtype
        that is still legitimate — per-bucket f32 scales ship
        ``chunk/block`` elements per destination, and scalar loss /
        all-finite-predicate reductions stay.  Anything wider-typed and
        larger is a gradient payload that escaped the compressed wire
        (the `compress-wire` lint)."""
        return {
            "wire": self.cfg.wire,
            "wire_itemsize": self.cfg.wire_itemsize,
            "n_buckets": self.n_buckets,
            "max_wide_operand_elems": max(
                (self.chunk // self.block) * self.n, 16
            ),
        }

    # --- error-feedback state --------------------------------------------

    def init_residual(self, mesh=None, axis_name: str = DEFAULT_AXIS):
        """The zero residual: globally ``(n, n, K_pad)`` f32, sharded over
        the data axis (rank r's block is ITS ``(n, K_pad)`` local error —
        per-rank state, never synced).  With ``mesh=None`` returns the
        uncommitted array (tests/manual shard_map harnesses)."""
        shape = (self.n, self.n, self.K_pad)
        if mesh is None:
            return jnp.zeros(shape, jnp.float32)
        return _sharded_zeros(shape, mesh, axis_name)


def _sharded_zeros(shape, mesh, axis_name: str = DEFAULT_AXIS):
    """Zeros born sharded P(axis) — never materializing the global array
    on one device (the residual is n× a gradient; a transient global
    allocation would OOM a chip at pod scale)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(axis_name))
    return jax.jit(
        lambda: jnp.zeros(shape, jnp.float32), out_shardings=sharding
    )()


def init_ef_state(template: Any, n: int, cfg: CompressConfig, mesh=None,
                  axis_name: str = DEFAULT_AXIS) -> dict:
    """The error-feedback state the compressed step builders thread
    through the optimizer-state slot: ``{"residual": (n, n, K_pad)
    sharded, "err": scalar}`` — ``err`` is the last step's relative
    quantization error (the `compression_error` gauge's source)."""
    plan = FlatPlan(template, n, cfg)
    err = jnp.zeros((), jnp.float32)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        # committed replicated scalar: an uncommitted device-0 scalar
        # round-trips through sharded checkpoints committed, clashing
        # with the mesh-wide step at dispatch (see fsdp._commit_scalars)
        err = jax.device_put(err, NamedSharding(mesh, P()))
    return {"residual": plan.init_residual(mesh, axis_name), "err": err}


def wrap_opt_state(inner, template: Any, n: int, cfg: CompressConfig,
                   mesh=None, axis_name: str = DEFAULT_AXIS) -> dict:
    """The ``{"opt", "ef"}`` opt-state wrapper around a single-axis EF
    state — ONE constructor for manual shard_map harnesses and tests
    (the ENGINE builds its own wrapper via `init_engine_ef_state`).
    ``inner`` is the (already placed) optimizer state; ``template``
    supplies the gradient shapes."""
    return {
        "opt": inner,
        "ef": init_ef_state(template, n, cfg, mesh, axis_name),
    }


def reset_resized_residual(opt_state, meta: dict, *,
                           axis_name: str = DEFAULT_AXIS):
    """Zero a restored EF residual whose SAVED shape differs from the
    live one (checkpoint from a different world size).

    `train.checkpoint.restore_fsdp`'s world-size translation flat-copies
    leaves — valid for fsdp's zero-padded rows, but the residual is
    dense per-(owner rank, destination) state whose rows would land on
    the wrong pairs.  Starting from a zero residual merely re-pays one
    step of quantization error; a misdirected one injects garbage.
    ``meta`` is the checkpoint's `read_meta` dict; returns ``opt_state``
    (with a fresh zero residual when the shapes differ)."""
    if not (isinstance(opt_state, dict) and "ef" in opt_state):
        return opt_state
    res = opt_state["ef"]["residual"]
    for rec in meta.get("leaves", ()):
        if rec["path"].endswith("['ef']['residual']"):
            if tuple(rec["shape"]) != tuple(res.shape):
                zeros = jax.jit(
                    lambda: jnp.zeros(res.shape, res.dtype),
                    out_shardings=res.sharding,
                )()
                return {
                    **opt_state,
                    "ef": {**opt_state["ef"], "residual": zeros},
                }
            break
    return opt_state


def ef_error(opt_state) -> float | None:
    """The last compressed sync's relative quantization error from a
    wrapped ``{"opt", "ef"}`` optimizer state (the `compression_error`
    gauge's source; None when the state carries no EF wrapper).  Reading
    it syncs one replicated device scalar — call at drained boundaries."""
    if isinstance(opt_state, dict) and "ef" in opt_state:
        return float(opt_state["ef"]["err"])
    return None


def engine_residual_spec(data_axes, model_axes=()):
    """PartitionSpec of the ENGINE's EF residual: globally ``(n_data,
    n_data, K_pad · n_model)`` with dim 0 sharded over the composed data
    axes (rank r's block is ITS local error) and the K dim sharded over
    the model axes (each model shard carries the residual of ITS slice
    of every gradient leaf — the wire compresses tp-sharded grads at
    their shard shape)."""
    from jax.sharding import PartitionSpec as P

    d = tuple(data_axes)
    m = tuple(model_axes)
    return P(
        d if len(d) > 1 else d[0],
        None,
        (m if len(m) > 1 else m[0]) if m else None,
    )


def init_engine_ef_state(
    plan: "FlatPlan", mesh, data_axes, model_axes=()
) -> dict:
    """The engine's error-feedback state (`make_partitioned_train_step
    (compress=...)`): ``{"residual", "err"}`` with the residual born
    sharded per `engine_residual_spec` — ``plan`` is the engine's
    FlatPlan over MODEL-LOCAL leaf shapes, so its ``K_pad`` is the
    per-model-shard row length and the global K dim is ``K_pad`` times
    the model-axis size."""
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    model_k = (
        int(np.prod([int(mesh.shape[a]) for a in model_axes]))
        if model_axes
        else 1
    )
    shape = (plan.n, plan.n, plan.K_pad * model_k)
    sharding = NamedSharding(mesh, engine_residual_spec(data_axes, model_axes))
    residual = jax.jit(
        lambda: jnp.zeros(shape, jnp.float32), out_shardings=sharding
    )()
    err = jax.device_put(
        jnp.zeros((), jnp.float32), NamedSharding(mesh, P())
    )
    return {"residual": residual, "err": err}


# ---------------------------------------------------------------------------
# Quantization (per-block scales)
# ---------------------------------------------------------------------------


def _quant_blocks(x: jax.Array, cfg: CompressConfig):
    """Quantize ``x`` (last dim a multiple of the block) with one scale
    per block.  Returns ``(q, scales)``; bfloat16 is scale-free
    (``scales`` is None)."""
    wire, maxv = _wire_spec(cfg.wire)
    if maxv is None:  # bf16: the cast is the whole codec
        return x.astype(wire), None
    shape = x.shape
    blocks = x.reshape(shape[:-1] + (shape[-1] // cfg.block, cfg.block))
    scales = jnp.max(jnp.abs(blocks), axis=-1) / maxv + 1e-30
    scaled = blocks / scales[..., None]
    if cfg.wire == "int8":
        q = jnp.clip(jnp.round(scaled), -maxv, maxv).astype(wire)
    else:  # fp8: the cast rounds; clip guards the saturating edge
        q = jnp.clip(scaled, -maxv, maxv).astype(wire)
    return q.reshape(shape), scales


def _dequant_blocks(q: jax.Array, scales, cfg: CompressConfig) -> jax.Array:
    if scales is None:
        return q.astype(jnp.float32)
    shape = q.shape
    blocks = q.astype(jnp.float32).reshape(
        shape[:-1] + (shape[-1] // cfg.block, cfg.block)
    )
    return (blocks * scales[..., None]).reshape(shape)


def _nonfinite_count(x: jax.Array) -> jax.Array:
    return jnp.sum(~jnp.isfinite(x)).astype(jnp.float32)


# ---------------------------------------------------------------------------
# The compressed collectives (inside shard_map)
# ---------------------------------------------------------------------------


def all_reduce_rows(
    rows: jax.Array,
    residual: jax.Array | None,
    plan: FlatPlan,
    axis_name: str = DEFAULT_AXIS,
    *,
    predicate_axes=None,
):
    """Bucketed quantized all-reduce of an ``(n, K_pad)`` row matrix.

    ``axis_name`` may be one mesh axis or a TUPLE of axes (the engine
    reduces over composed data axes, e.g. ``('dp', 'fsdp')``) — every
    collective inside treats the tuple as one flattened axis.

    Returns ``(sum_rows, new_residual, stats)`` — ``sum_rows`` is the
    cross-rank SUM (callers divide by n for the mean), ``new_residual``
    is None iff ``residual`` was, and ``stats`` is ``{"err": relative
    quantization error (pmean'd), "ok": all-finite predicate}``.  On a
    globally non-finite input the output rows are NaN (so a NaN guard
    trips exactly as under exact sync) and the residual is held
    unchanged — a skipped step must not absorb a poisoned residual.
    ``predicate_axes`` widens the all-finite reduction (default: the
    reduction axes) — the engine passes data+model axes so a NaN on one
    model shard poisons the WHOLE step, not one tp slice of it.
    """
    cfg = plan.cfg
    acc = rows + residual if residual is not None else rows
    ok = lax.psum(
        _nonfinite_count(acc),
        predicate_axes if predicate_axes is not None else axis_name,
    ) == 0
    q, scales = _quant_blocks(acc, cfg)
    deq = _dequant_blocks(q, scales, cfg)
    err1 = acc - deq  # this rank's first-round quantization error
    c, nb = plan.chunk, plan.n_buckets
    out_parts, err2_parts = [], []
    for j in range(nb):  # ONE wire exchange per bucket
        sl = slice(j * c, (j + 1) * c)
        qj = lax.all_to_all(
            q[:, sl], axis_name, split_axis=0, concat_axis=0, tiled=True
        )
        sj = None
        if scales is not None:
            sj = lax.all_to_all(
                scales[:, j * (c // plan.block): (j + 1) * (c // plan.block)],
                axis_name, split_axis=0, concat_axis=0, tiled=True,
            )
        reduced = _dequant_blocks(qj, sj, cfg).sum(axis=0)  # (c,) exact f32
        q2, s2 = _quant_blocks(reduced, cfg)
        err2_parts.append(reduced - _dequant_blocks(q2, s2, cfg))
        qa = lax.all_gather(q2, axis_name, axis=0)  # (n, c) 1-byte wire
        sa = (
            lax.all_gather(s2, axis_name, axis=0) if s2 is not None else None
        )
        out_parts.append(_dequant_blocks(qa, sa, cfg))
    total = jnp.concatenate(out_parts, axis=1)  # (n, K_pad) cross-rank sum
    err = jnp.linalg.norm(err1) / (jnp.linalg.norm(acc) + 1e-12)
    stats = {"err": lax.pmean(jnp.where(ok, err, jnp.nan), axis_name), "ok": ok}
    total = jnp.where(ok, total, jnp.nan)
    if residual is None:
        return total, None, stats
    # Rank r alone knows the second-round error of chunk r — feed it back
    # into r's own next contribution so BOTH rounds are error-compensated.
    r = lax.axis_index(axis_name)
    err2 = jnp.concatenate(err2_parts)  # (K_pad,)
    own = lax.dynamic_slice_in_dim(err1, r, 1, axis=0) + err2[None]
    new_residual = lax.dynamic_update_slice_in_dim(err1, own, r, axis=0)
    new_residual = jnp.where(ok, new_residual, residual)
    return total, new_residual, stats


def reduce_scatter_rows(
    rows: jax.Array,
    residual: jax.Array | None,
    plan: FlatPlan,
    axis_name: str = DEFAULT_AXIS,
):
    """Bucketed quantized reduce-scatter: each rank ends with ITS
    f32-reduced ``(K_pad,)`` row (cross-rank SUM of row r) — the
    compressed form of the fsdp/zero1 ``psum_scatter`` hop, at half the
    allreduce's wire cost and with a single quantization round (the
    reduction itself is exact f32).  Same EF / non-finite contract as
    `all_reduce_rows`; returns ``(local_row, new_residual, stats)``."""
    cfg = plan.cfg
    acc = rows + residual if residual is not None else rows
    ok = lax.psum(_nonfinite_count(acc), axis_name) == 0
    q, scales = _quant_blocks(acc, cfg)
    err1 = acc - _dequant_blocks(q, scales, cfg)
    c, nb = plan.chunk, plan.n_buckets
    parts = []
    for j in range(nb):
        sl = slice(j * c, (j + 1) * c)
        qj = lax.all_to_all(
            q[:, sl], axis_name, split_axis=0, concat_axis=0, tiled=True
        )
        sj = None
        if scales is not None:
            sj = lax.all_to_all(
                scales[:, j * (c // plan.block): (j + 1) * (c // plan.block)],
                axis_name, split_axis=0, concat_axis=0, tiled=True,
            )
        parts.append(_dequant_blocks(qj, sj, cfg).sum(axis=0))
    local = jnp.concatenate(parts)  # (K_pad,) this rank's reduced row
    err = jnp.linalg.norm(err1) / (jnp.linalg.norm(acc) + 1e-12)
    stats = {"err": lax.pmean(jnp.where(ok, err, jnp.nan), axis_name), "ok": ok}
    local = jnp.where(ok, local, jnp.nan)
    if residual is None:
        return local, None, stats
    new_residual = jnp.where(ok, err1, residual)
    return local, new_residual, stats


# ---------------------------------------------------------------------------
# Convenience wrappers (demos / benchmarks / tests)
# ---------------------------------------------------------------------------


def compressed_all_reduce(
    x: jax.Array,
    cfg: CompressConfig | str = "int8",
    axis_name: str = DEFAULT_AXIS,
) -> jax.Array:
    """Stateless bucketed quantized all-reduce of ONE array (sum
    semantics, like `comm.all_reduce`) — the demo/bench entry point; the
    trainers use the residual-threading row forms directly."""
    cfg = parse(cfg)
    if cfg is None:
        return lax.psum(x, axis_name)
    plan = FlatPlan(x, lax.axis_size(axis_name), cfg)
    total, _, _ = all_reduce_rows(plan.to_rows(x), None, plan, axis_name)
    return plan.from_rows(total)
