"""Rule-driven partition engine: regex rules over flattened parameter
paths → `PartitionSpec`s, and ONE sharded train step for any composed
dp×fsdp×tp mesh.

Why this exists (ROADMAP item 2): the sharding decision used to live in
three separate step builders — replicated DP (`data_parallel`), flat-row
FSDP/ZeRO-1 (`fsdp`) — each a hand-written shard_map program, which is
why the trainers refuse most mode compositions (pipeline×fsdp,
compress×TP, ...): every pair of strategies is a new code path.  Here
the strategy is DATA, not code:

- `match_partition_rules(rules, tree, mesh)` maps ``(regex, spec)``
  rules over '/'-joined tree paths (the `fmengine`/EasyLM pattern,
  SNIPPETS.md [1]) to a `PartitionSpec` pytree — scalars and size-1
  leaves fall back to replicated, axes that don't divide a dim are
  dropped per-leaf, first match wins.
- `make_partitioned_train_step` compiles the GLOBAL train step under
  ``jax.jit`` with those specs as in/out shardings and lets XLA's SPMD
  partitioner derive every collective (the GSPMD form of
  `make_train_step_auto`, extended to sharded state).  The weight
  update is constrained to the OPT-STATE rules, so optimizer state and
  the update math run sharded — automatic cross-replica sharding of the
  weight update per PAPERS.md (arxiv 2004.13336): ZeRO-1 is a rule set,
  not a step builder ("zero1-for-free on any dp axis").
- `resolve_rules("dp=2,fsdp=2")` (or ``zero1:dp=8``, ``dp=2,tp=2``, ...)
  re-expresses data_parallel / fsdp / zero1 as built-in rule sets and
  composes them with a Megatron-layout ``tp`` vocabulary for
  `TransformerLM` — 2-D/3-D meshes come from one config knob
  (`TrainConfig.mesh_axes` / `LMTrainConfig.mesh_axes`), and per-layer
  overrides ride user rules (the config's ``partition_rules`` list)
  matched FIRST.

Numerics: the partitioned program is the SAME global math, partitioned —
grads/opt-state match the strategy implementations to fp tolerance
(tests/test_partition.py pins dp/fsdp/zero1 and the composed meshes
against the legacy builders and the dense reference).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_dist.observe import spans
from tpu_dist.parallel.data_parallel import partitioned_over

DP_AXIS = "dp"
FSDP_AXIS = "fsdp"
TP_AXIS = "tp"
KNOWN_AXES = (DP_AXIS, FSDP_AXIS, TP_AXIS)

__all__ = [
    "DP_AXIS",
    "FSDP_AXIS",
    "TP_AXIS",
    "RuleSet",
    "PartitionedTrainStep",
    "build_mesh",
    "dead_user_rules",
    "match_partition_rules",
    "make_partitioned_train_step",
    "make_shard_and_gather_fns",
    "gather_replicated",
    "parse_mesh_axes",
    "partition_summary",
    "per_device_bytes",
    "state_bytes_by_class",
    "resolve_rules",
    "resolve_trainer_rules",
    "rule_match_report",
    "shard_over",
    "strategy_engine_spec",
    "tree_paths",
]


# --------------------------------------------------------------- tree paths


def _key_name(k) -> str:
    """One path component of a tree_flatten_with_path key entry."""
    for attr in ("key", "idx", "name"):
        v = getattr(k, attr, None)
        if v is not None:
            return str(v)
    return str(k)


def tree_paths(tree: Any) -> list[tuple[str, Any]]:
    """``[('blocks/0/mlp/fc1/w', leaf), ...]`` — the '/'-joined flat
    paths the rule regexes match against (``re.search``, so a rule like
    ``mlp/fc1/w$`` matches the same parameter inside ANY wrapper tree,
    including optimizer-state subtrees like ``m/blocks/0/mlp/fc1/w``)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [("/".join(_key_name(k) for k in kp), leaf) for kp, leaf in flat]


# ------------------------------------------------------------ spec fitting


def _axis_size(mesh: Mesh, name: str) -> int:
    try:
        return int(mesh.shape[name])
    except KeyError:
        raise ValueError(
            f"partition rule names mesh axis {name!r}, but the mesh axes "
            f"are {tuple(mesh.axis_names)}"
        ) from None


def _fit_spec(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Validate ``spec`` against a concrete leaf: unknown axis names
    raise; an axis whose size does not divide its dim is DROPPED (the
    small-leaf fallback — a 1-D bias too small for the fsdp axis simply
    stays replicated); a spec longer than the leaf's rank raises."""
    entries = tuple(spec)
    if len(entries) > len(shape):
        raise ValueError(
            f"partition spec {spec} has {len(entries)} entries for a "
            f"leaf of shape {shape}"
        )
    out = []
    for dim, entry in enumerate(entries):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        kept, prod = [], 1
        for name in names:
            size = _axis_size(mesh, name)
            if shape[dim] % (prod * size) == 0:
                kept.append(name)
                prod *= size
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _greedy_assign(
    shape: tuple[int, ...], axes: Sequence[str], mesh: Mesh, init: P = P()
) -> P:
    """Assign ``axes`` (in order) to dims of ``shape``, largest
    divisible dim first, starting from the ``init`` spec.  An axis that
    fits nowhere (or already appears in ``init``) is skipped — the
    replicated fallback the engine promises for small leaves."""
    entries: list[Any] = [
        (e if isinstance(e, tuple) else (e,)) if e is not None else ()
        for e in tuple(init)
    ]
    entries += [()] * (len(shape) - len(entries))
    used = {name for e in entries for name in e}
    for axis in axes:
        if axis in used:
            continue
        size = _axis_size(mesh, axis)
        # prefer the largest per-shard dim (dim size / what's already
        # assigned there), unsharded dims before stacking onto sharded
        best = None
        for dim in range(len(shape)):
            prod = int(np.prod([_axis_size(mesh, n) for n in entries[dim]] or [1]))
            if shape[dim] % (prod * size):
                continue
            key = (len(entries[dim]) == 0, shape[dim] // prod)
            if best is None or key > best[0]:
                best = (key, dim)
        if best is not None:
            entries[best[1]] = tuple(entries[best[1]]) + (axis,)
            used.add(axis)
    out = [
        tuple(e) if len(e) > 1 else (e[0] if e else None) for e in entries
    ]
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def shard_over(*axes: str) -> Callable:
    """Rule value: shard the leaf over ``axes``, each axis greedily
    placed on the largest dim it divides (replicated when nothing
    divides) — the generic fsdp/zero1 rule."""

    def rule(path, leaf, mesh):
        return _greedy_assign(tuple(leaf.shape), axes, mesh)

    return rule


def _fill(value, axes: tuple[str, ...]) -> Callable:
    """Wrap a rule value so the resulting spec is EXTENDED by ``axes``
    on remaining dims — how a param rule becomes its sharded-update/
    opt-state rule (`zero1`-for-free: the update additionally shards
    over the data axes the gradient was reduced over)."""

    def rule(path, leaf, mesh):
        base = _apply_rule_value(value, path, leaf, mesh)
        return _greedy_assign(tuple(leaf.shape), axes, mesh, base)

    return rule


def _apply_rule_value(value, path, leaf, mesh) -> P:
    if callable(value):
        spec = value(path, leaf, mesh)
    elif isinstance(value, str):
        spec = _parse_spec(value)
    else:
        spec = value
    return _fit_spec(spec, tuple(leaf.shape), mesh)


# ------------------------------------------------------------ rule matching


def _match_leaves(rules, tree: Any, mesh: Mesh) -> tuple[list, Any]:
    """The matching core: ``([(path, shape, rule_index, spec), ...],
    treedef)`` in leaf order.  ``rule_index`` is None for scalar/size-1
    leaves (replicated unconditionally, no rule consulted)."""
    rules = tuple(rules)
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        path = "/".join(_key_name(k) for k in kp)
        shape = tuple(getattr(leaf, "shape", ()))
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            out.append((path, shape, None, P()))  # scalars replicate
            continue
        for idx, (pattern, value) in enumerate(rules):
            if re.search(pattern, path) is not None:
                out.append(
                    (path, shape, idx, _apply_rule_value(value, path, leaf, mesh))
                )
                break
        else:
            raise ValueError(
                f"no partition rule matched leaf {path!r} "
                f"(shape {shape}); add a catch-all ('.*', P()) rule"
            )
    return out, treedef


def match_partition_rules(rules, tree: Any, mesh: Mesh) -> Any:
    """`PartitionSpec` pytree for ``tree``: first rule whose regex
    ``re.search``-matches the leaf's '/'-joined path wins; scalar and
    size-1 leaves are replicated unconditionally; a leaf no rule matches
    raises (built-in rule sets always end with a catch-all).

    ``rules``: iterable of ``(pattern, value)`` where value is a
    `PartitionSpec`, a spec string (see `_parse_spec`), or a callable
    ``(path, leaf, mesh) -> PartitionSpec`` (e.g. `shard_over`)."""
    matched, treedef = _match_leaves(rules, tree, mesh)
    return jax.tree_util.tree_unflatten(
        treedef, [spec for _, _, _, spec in matched]
    )


def rule_match_report(rules, tree: Any, mesh: Mesh) -> dict:
    """Which rule claimed which leaf — the raw material for the static
    analyzer's dead-rule / replicated-fallthrough lints and for
    debugging a rule set by hand.

    Returns ``{"leaves": [{"path", "shape", "rule", "pattern", "spec",
    "replicated"}, ...], "counts": [matches per rule], "dead": [indices
    of rules that matched nothing]}``.  ``rule`` is None for the
    scalar/size-1 leaves no rule is consulted for."""
    rules = tuple(rules)
    matched, _ = _match_leaves(rules, tree, mesh)
    counts = [0] * len(rules)
    leaves = []
    for path, shape, idx, spec in matched:
        if idx is not None:
            counts[idx] += 1
        leaves.append(
            {
                "path": path,
                "shape": shape,
                "rule": idx,
                "pattern": rules[idx][0] if idx is not None else None,
                "spec": spec,
                "replicated": all(e is None for e in tuple(spec)),
            }
        )
    return {
        "leaves": leaves,
        "counts": counts,
        "dead": [i for i, c in enumerate(counts) if c == 0],
    }


def dead_user_rules(
    rules: "RuleSet", tree: Any, mesh: Mesh, *, opt_tree: Any = None
) -> tuple[str, ...]:
    """Patterns among the USER rules (env + config, the first
    ``rules.n_user`` entries) that match no leaf of ``tree`` — a typo'd
    pattern silently falling through to the built-ins is the classic way
    a "pinned" layer ends up sharded wrong.  Dead BUILT-IN rules are
    normal (the tp vocabulary matches nothing on a conv net) and are not
    reported here.  User rules also apply to the optimizer state (whose
    paths carry wrapper prefixes like ``buf/``), so pass ``opt_tree`` to
    clear rules that legitimately pin only an opt-state leaf."""
    if not rules.n_user:
        return ()
    dead = set(rule_match_report(rules.param_rules, tree, mesh)["dead"])
    if opt_tree is not None and dead:
        dead &= set(
            rule_match_report(rules.opt_rules, opt_tree, mesh)["dead"]
        )
    return tuple(
        rules.param_rules[i][0] for i in sorted(dead) if i < rules.n_user
    )


# ----------------------------------------------------------- rule parsing


def _parse_spec(text: str) -> P:
    """``'None,tp'`` → ``P(None, 'tp')``; ``'dp+fsdp'`` → one dim
    sharded by both axes; ``'replicated'`` / ``''`` → ``P()``."""
    text = text.strip()
    if text in ("", "replicated", "P()"):
        return P()
    entries = []
    for part in text.split(","):
        part = part.strip()
        if part in ("None", "-", ""):
            entries.append(None)
        elif "+" in part:
            entries.append(tuple(p.strip() for p in part.split("+")))
        else:
            entries.append(part)
    return P(*entries)


def _normalize_user_rules(user_rules) -> tuple:
    out = []
    for pattern, value in user_rules or ():
        out.append(
            (pattern, _parse_spec(value) if isinstance(value, str) else value)
        )
    return tuple(out)


# --------------------------------------------------------------- rule sets


@dataclass(frozen=True)
class RuleSet:
    """A named partition strategy: rules for params, rules for the
    optimizer state / weight update, which mesh axes shard the batch
    (gradients reduce over these), and which shard the MODEL in a
    non-data way (the tensor-parallel axes other subsystems — e.g.
    `comm.compress` — must refuse)."""

    name: str
    param_rules: tuple
    opt_rules: tuple
    data_axes: tuple[str, ...]
    model_axes: tuple[str, ...] = ()
    # how many leading entries of param_rules/opt_rules came from the
    # user (env + config) — the slice `dead_user_rules` audits
    n_user: int = 0

    def batch_spec(self) -> P:
        """Batch partition: leading dim sharded over every data axis."""
        if not self.data_axes:
            return P()
        if len(self.data_axes) == 1:
            return P(self.data_axes[0])
        return P(tuple(self.data_axes))


def _p_rule(*entries) -> Callable:
    """Fixed-layout rule value (divisibility still fitted per leaf)."""
    spec = P(*entries)

    def rule(path, leaf, mesh):
        return _fit_spec(spec, tuple(leaf.shape), mesh)

    return rule


def _megatron_rules(tp: str) -> tuple:
    """The Megatron layout over `TransformerLM`/`EncoderBlock` params:
    column-parallel QKV/fc1 (output dim sharded), row-parallel out/fc2
    (input dim sharded), vocab-sharded embedding table; norms/positions
    replicated via the caller's catch-all."""
    return (
        (r"attn/qkv/w$", _p_rule(None, tp)),
        (r"attn/qkv/b$", _p_rule(tp)),
        (r"attn/(q|kv)/w$", _p_rule(None, tp)),
        (r"attn/(q|kv)/b$", _p_rule(tp)),
        (r"attn/out/w$", _p_rule(tp, None)),
        (r"mlp/fc1/w$", _p_rule(None, tp)),
        (r"mlp/fc1/b$", _p_rule(tp)),
        (r"mlp/fc2/w$", _p_rule(tp, None)),
        (r"embed/table$", _p_rule(tp, None)),
    )


def parse_mesh_axes(spec: str) -> tuple[str | None, dict[str, int | None]]:
    """``'dp=2,fsdp=4'`` / ``'zero1:dp=8'`` / ``'dp=2,tp=2'`` →
    ``(prefix_or_None, {axis: size_or_None})``.  Sizes may be omitted
    (``'dp,fsdp'``) and are then taken from the mesh at resolve time."""
    prefix = None
    body = spec.strip()
    if ":" in body:
        prefix, body = (s.strip() for s in body.split(":", 1))
        if prefix != "zero1":
            raise ValueError(
                f"unknown rule-set prefix {prefix!r} in mesh_axes "
                f"{spec!r} — only 'zero1:' is recognized"
            )
    axes: dict[str, int | None] = {}
    for part in body.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, size = part.partition("=")
        name = name.strip()
        if name not in KNOWN_AXES:
            raise ValueError(
                f"unknown mesh axis {name!r} in mesh_axes {spec!r} — "
                f"known axes are {KNOWN_AXES}"
            )
        if name in axes:
            raise ValueError(f"duplicate axis {name!r} in mesh_axes {spec!r}")
        axes[name] = int(size) if size else None
    if not axes:
        raise ValueError(f"mesh_axes {spec!r} names no axes")
    if DP_AXIS not in axes and FSDP_AXIS not in axes:
        raise ValueError(
            f"mesh_axes {spec!r} has no data axis — include 'dp' or "
            "'fsdp' (the batch must shard over something)"
        )
    if prefix == "zero1" and FSDP_AXIS in axes:
        raise ValueError(
            "zero1: is redundant with an fsdp axis (fsdp already shards "
            "params AND optimizer state) — drop one"
        )
    return prefix, axes


def build_mesh(
    spec: str,
    *,
    platform: str | None = None,
    mesh_devices=None,
) -> Mesh:
    """A `Mesh` shaped by a mesh_axes spec (sizes required here, except
    that ONE axis may omit its size and absorbs the remaining devices)."""
    from tpu_dist.comm import mesh as mesh_mod

    _, axes = parse_mesh_axes(spec)
    devs = (
        list(mesh_devices)
        if mesh_devices is not None
        else mesh_mod.devices(platform)
    )
    free = [a for a, s in axes.items() if s is None]
    if len(free) > 1:
        raise ValueError(
            f"build_mesh({spec!r}): at most one axis may omit its size"
        )
    if free:
        known = int(np.prod([s for s in axes.values() if s is not None]))
        if len(devs) % known:
            raise ValueError(
                f"build_mesh({spec!r}): {len(devs)} devices not divisible "
                f"by the explicit axis product {known}"
            )
        axes[free[0]] = len(devs) // known
    return mesh_mod.make_mesh(
        tuple(axes.values()), tuple(axes.keys()),
        platform=platform, mesh_devices=mesh_devices,
    )


def enumerate_mesh_axes(
    n_chips: int,
    *,
    tp: bool = False,
    zero1: bool = True,
) -> list[str]:
    """Every built-in mesh_axes spec expressible at ``n_chips`` chips —
    the candidate space `analysis.advisor` ranks statically.

    Covers the single-axis rule sets (``dp=N``, ``zero1:dp=N``,
    ``fsdp=N``) plus every 2-axis factorization of the chip count:
    ``dp=a,fsdp=b`` always, ``dp=a,tp=b`` when ``tp=True`` (the
    Megatron vocabulary only binds to transformer parameter names —
    pointless for models it cannot shard).  Each spec resolves through
    `resolve_rules` on a `build_mesh` of that shape, so the enumeration
    and the engine can never disagree about what a candidate means.
    Deterministic order (the advisor's tie-break)."""
    n = int(n_chips)
    if n < 1:
        raise ValueError(f"need at least one chip, got {n}")
    specs = [f"dp={n}"]
    if n >= 2:
        if zero1:
            specs.append(f"zero1:dp={n}")
        specs.append(f"fsdp={n}")
    for a in range(2, n):
        if n % a:
            continue
        b = n // a
        if b < 2:
            continue
        specs.append(f"dp={a},fsdp={b}")
        if tp:
            specs.append(f"dp={a},tp={b}")
    return specs


def resolve_rules(
    spec: str,
    mesh: Mesh,
    *,
    user_rules=None,
    bind: dict[str, str] | None = None,
) -> RuleSet:
    """The `RuleSet` for a mesh_axes spec, validated against ``mesh``.

    Built-in sets (derived from the axes present):

    - ``'dp=N'`` — everything replicated; the reference data-parallel
      baseline (the replicated weight update the bench compares against).
    - ``'zero1:dp=N'`` — params replicated, optimizer state + update
      sharded over dp (ZeRO-1 as data).
    - ``'fsdp=N'`` / ``'dp=A,fsdp=B'`` — params sharded over fsdp
      (largest divisible dim per leaf), opt state additionally over dp.
    - ``'dp=A,tp=B'`` (± fsdp) — Megatron-layout TP rules for the
      transformer param names, fsdp/catch-all for the rest; opt state
      picks up the dp axis (sharded update on every set but pure dp).

    ``bind`` maps spec ROLE names onto the mesh's actual axis names
    (e.g. ``{"fsdp": "data"}`` runs the fsdp rule set on a mesh whose
    axis is called ``data``) — how the trainers route their legacy
    fsdp/zero1/dp flags through the engine on the caller's existing
    mesh without renaming its axes.  The `RuleSet`'s ``name`` (and
    therefore checkpoint/telemetry provenance) stays role-based;
    ``data_axes``/``model_axes`` and every rule carry the BOUND names.

    ``user_rules`` (list of ``(pattern, spec)``) are matched ahead of
    the built-ins — so a single layer can be pinned to a different spec
    without forking the rule set.  User rules apply to
    params AND optimizer state (the update follows the pinned layout).
    """
    prefix, axes = parse_mesh_axes(spec)
    bind = dict(bind or {})
    if set(bind) - set(axes):
        raise ValueError(
            f"bind maps roles {sorted(set(bind) - set(axes))} that the "
            f"mesh_axes spec {spec!r} does not name"
        )
    # role -> actual mesh axis name (identity unless bound)
    actual = {role: bind.get(role, role) for role in axes}
    mesh_shape = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    want = {
        actual[a]: (s if s is not None else mesh_shape.get(actual[a]))
        for a, s in axes.items()
    }
    if tuple(want) != tuple(mesh.axis_names) or any(
        mesh_shape.get(a) != s for a, s in want.items()
    ):
        raise ValueError(
            f"mesh_axes {spec!r} (axes {want}) does not match the mesh "
            f"(axes {mesh_shape}) — build the mesh with "
            f"partition.build_mesh({spec!r}) or align the spec"
        )
    has_fsdp = FSDP_AXIS in axes
    has_tp = TP_AXIS in axes
    fsdp_ax, tp_ax = actual.get(FSDP_AXIS), actual.get(TP_AXIS)
    data_axes = tuple(
        actual[a] for a in axes if a in (DP_AXIS, FSDP_AXIS)
    )

    catch_all = shard_over(fsdp_ax) if has_fsdp else _p_rule()
    if has_tp:
        param_rules = _megatron_rules(tp_ax)
        if has_fsdp:  # 2-D weight sharding: tp dim + fsdp on the rest
            param_rules = tuple(
                (pat, _fill(val, (fsdp_ax,))) for pat, val in param_rules
            )
        param_rules += ((r".*", catch_all),)
    else:
        param_rules = ((r".*", catch_all),)

    # The sharded weight update: pure dp keeps the replicated update
    # (the baseline); every other set extends the param layout by the
    # data axes — optimizer state born 1/|dp| (ZeRO-1 for free).
    name = prefix or "+".join(axes)
    plain_dp = name == DP_AXIS and not has_fsdp and not has_tp
    if plain_dp:
        opt_rules = param_rules
    else:
        update_axes = (actual[DP_AXIS],) if DP_AXIS in axes else ()
        opt_rules = tuple(
            (pat, _fill(val, update_axes)) for pat, val in param_rules
        )
    user = _normalize_user_rules(user_rules)
    return RuleSet(
        name=name,
        param_rules=user + tuple(param_rules),
        opt_rules=user + tuple(opt_rules),
        data_axes=data_axes,
        model_axes=(tp_ax,) if has_tp else (),
        n_user=len(user),
    )


def partition_summary(rules: RuleSet, mesh: Mesh) -> dict:
    """JSON-able provenance for telemetry / checkpoint metadata."""
    return {
        "rules": rules.name,
        "axes": {str(k): int(v) for k, v in dict(mesh.shape).items()},
        "data_axes": list(rules.data_axes),
        "model_axes": list(rules.model_axes),
    }


def strategy_engine_spec(
    mesh: Mesh,
    *,
    fsdp: bool = False,
    zero1: bool = False,
    data_axis: str,
    tp_axis: str | None = None,
) -> tuple[str, dict[str, str]]:
    """The ``(mesh_axes spec, bind)`` pair that routes the retired
    fsdp/zero1/dp trainer FLAGS through the engine on the caller's
    existing mesh — one synthesis for both trainers, so the flag→rule
    translation cannot drift between them.  ``data_axis`` is the mesh's
    batch axis (the legacy builders' ``'data'``); ``tp_axis`` composes
    the Megatron tp vocabulary (the tensor_parallel flag's model axis).
    Neither flag set means plain dp."""
    if fsdp and zero1:
        raise ValueError("fsdp and zero1 are mutually exclusive")
    d = _axis_size(mesh, data_axis)
    role = FSDP_AXIS if fsdp else DP_AXIS
    prefix = "zero1:" if zero1 else ""
    spec = f"{prefix}{role}={d}"
    bind = {role: data_axis}
    if tp_axis is not None:
        spec += f",tp={_axis_size(mesh, tp_axis)}"
        bind[TP_AXIS] = tp_axis
    return spec, bind


def resolve_trainer_rules(
    where: str,
    mesh: Mesh,
    mesh_axes: str,
    *,
    user_rules=None,
    bind: dict[str, str] | None = None,
) -> tuple[RuleSet, dict]:
    """The shared trainer-side resolution (`Trainer` and `LMTrainer`
    engine modes): rule set + checkpoint/telemetry summary.  The
    compressed gradient wire is part of the engine itself
    (`make_partitioned_train_step(compress=...)`), so there is no
    trainer-level compress refusal here anymore — the only remaining
    refusal (2-D model×data weight sharding) is raised by the step
    builder, naming the offending leaves."""
    rules = resolve_rules(mesh_axes, mesh, user_rules=user_rules, bind=bind)
    return rules, partition_summary(rules, mesh)


def gather_replicated(tree: Any, mesh: Mesh) -> Any:
    """Full (replicated) copies of a rule-sharded pytree, multi-host
    safe: fully-addressable trees pass through untouched (``np.asarray``
    on the leaves already works); otherwise one compiled identity with
    replicated out-shardings all-gathers every leaf — the engine-mode
    analog of `fsdp_full_params` for eval/generate paths."""
    if all(
        getattr(leaf, "is_fully_addressable", True)
        for leaf in jax.tree.leaves(tree)
    ):
        return tree
    repl = NamedSharding(mesh, P())
    return jax.jit(lambda t: t, out_shardings=repl)(tree)


# ------------------------------------------------------- shard/gather fns


def make_shard_and_gather_fns(specs: Any, mesh: Mesh) -> tuple[Any, Any]:
    """Per-leaf ``(shard_fns, gather_fns)`` for a `PartitionSpec` pytree
    (the SNIPPETS.md [3] pattern): ``shard_fns`` place host arrays under
    their `NamedSharding` (a fresh committed buffer — never an alias the
    donating step could invalidate); ``gather_fns`` fetch the full
    logical array back to host (single-controller: every shard must be
    addressable — use the checkpoint layer for multi-host gathers)."""

    def make_shard(spec):
        sharding = NamedSharding(mesh, spec)
        return lambda x: jax.device_put(np.asarray(x), sharding)

    def make_gather(_spec):
        return lambda x: np.asarray(jax.device_get(x))

    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    return (
        jax.tree_util.tree_map(make_shard, specs, is_leaf=is_spec),
        jax.tree_util.tree_map(make_gather, specs, is_leaf=is_spec),
    )


def per_device_bytes(tree: Any, device=None) -> int:
    """Bytes of ``tree`` resident on ONE device (default: the first
    device of the first leaf's sharding) — the honest per-chip cost of
    params/opt state under a rule set (a replicated leaf counts once, a
    sharded leaf counts its local shard)."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.ShapeDtypeStruct):
            # abstract leaves (analysis programs): logical bytes — the
            # caller's tree is single-device or already shard-shaped
            total += int(np.prod(leaf.shape)) * leaf.dtype.itemsize
            continue
        if not hasattr(leaf, "addressable_shards"):
            total += np.asarray(leaf).nbytes
            continue
        dev = device
        if dev is None:
            dev = sorted(leaf.sharding.device_set, key=lambda d: d.id)[0]
        total += sum(
            s.data.nbytes for s in leaf.addressable_shards if s.device == dev
        )
    return total


def state_bytes_by_class(params=None, opt_state=None, device=None,
                         **extra) -> list[dict]:
    """Per-device resident bytes bucketed into the classes an OOM (or a
    memory plan) should name: ``params``, ``opt``, and — when the
    optimizer state carries the compressed-wire EF wrapper — the
    ``ef_residual`` split out of ``opt`` (the residual is n× a gradient,
    so it deserves its own line).  Extra kwargs add caller-labeled trees
    (``batch=...``, ``weights=...``, ``kv_pool=...``).  Returns
    ``[{class, bytes}]`` rows, zero-byte classes dropped."""
    trees: list[tuple[str, Any]] = []
    if params is not None:
        trees.append(("params", params))
    if opt_state is not None:
        if isinstance(opt_state, dict) and "ef" in opt_state:
            ef = opt_state["ef"]
            trees.append(("opt", {k: v for k, v in opt_state.items()
                                  if k != "ef"}))
            trees.append(("ef_residual", ef.get("residual")))
        else:
            trees.append(("opt", opt_state))
    trees.extend(extra.items())
    rows = []
    for name, tree in trees:
        if tree is None:
            continue
        nbytes = per_device_bytes(tree, device)
        if nbytes:
            rows.append({"class": name, "bytes": int(nbytes)})
    return rows


# ----------------------------------------------------------- train step


def _strip_spec(spec: P, keep) -> P:
    """``spec`` restricted to axis names in ``keep`` (tuples filtered,
    empty entries -> None, trailing Nones trimmed) — how one leaf spec
    splits into its manual (data) and auto (model) components for the
    compressed-wire region."""
    keep = set(keep)
    out = []
    for e in tuple(spec):
        if e is None:
            out.append(None)
            continue
        names = e if isinstance(e, tuple) else (e,)
        kept = tuple(n for n in names if n in keep)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _local_shape(shape, spec: P, axes, mesh: Mesh) -> tuple[int, ...]:
    """The per-device shape of a leaf along ``axes`` only (other axis
    names in the spec are ignored)."""
    axes = set(axes)
    out = list(shape)
    for d, e in enumerate(tuple(spec)):
        if e is None:
            continue
        for nme in e if isinstance(e, tuple) else (e,):
            if nme in axes:
                out[d] //= _axis_size(mesh, nme)
    return tuple(out)


def _gather_axes(leaf: jax.Array, spec: P, axes) -> jax.Array:
    """Inside a manual region: all_gather the ``axes`` components of
    ``spec`` back to the full leaf (tiled, per dim) — the per-step
    un-shard of fsdp-ruled params the compressed region pays exactly
    like GSPMD's derived gathers do."""
    from jax import lax

    axes = set(axes)
    for d, e in enumerate(tuple(spec)):
        if e is None:
            continue
        names = tuple(
            n for n in (e if isinstance(e, tuple) else (e,)) if n in axes
        )
        if names:
            leaf = lax.all_gather(
                leaf, names if len(names) > 1 else names[0],
                axis=d, tiled=True,
            )
    return leaf


@dataclass
class PartitionedTrainStep:
    """What `make_partitioned_train_step` hands back: the compiled step
    plus the sharded live state and the resolved specs (checkpoint
    metadata, telemetry, tests)."""

    step: Callable
    params: Any
    opt_state: Any
    param_specs: Any
    opt_specs: Any
    ruleset: RuleSet
    mesh: Mesh = field(repr=False, default=None)
    # user-rule patterns that matched no parameter leaf (surfaced as a
    # warning event at build time and a `dead-rule` analyzer finding)
    dead_rules: tuple[str, ...] = ()
    # the resolved compressed-wire config + flat bucket plan (None when
    # the step syncs exact f32); plan shapes are per MODEL shard — the
    # wire accounting and `analysis_expectations` the telemetry and the
    # `compress-wire` lint consume
    compress: Any = None
    flat_plan: Any = field(repr=False, default=None)
    # what the step's trace was told and what attention became under it
    # (`parallel.partitioned_over`; empty until the step is traced)
    partitioned: Any = field(repr=False, default=None)
    _attention_reported: bool = field(repr=False, default=False)

    def summary(self) -> dict:
        return partition_summary(self.ruleset, self.mesh)

    def attention_form(self) -> dict | None:
        """What `nn.dot_product_attention` became in the traced step, for
        the calls flash takes: the form, the axes one `shard_map` splits
        batch and heads over, one device's shape, the number of calls and
        how often the per-device function's body was traced for them
        (once, whatever the depth: a number that follows the depth is a
        trace per layer, which costs set-up seconds).  None before the
        step is traced, and where no call was flash's to take."""
        seen = self.partitioned.attention if self.partitioned else ()
        if not seen:
            return None
        form, spec, shape = seen[0]
        return {
            "form": form if all(s[0] == form for s in seen) else "mixed",
            "axes": [list(e) if isinstance(e, tuple) else e for e in spec],
            "per_device_shape": list(shape),
            "calls": len(seen),
            "per_device_traces": self.partitioned.per_device_traces,
        }

    def report_attention(self, log: Callable[[str], None]) -> None:
        """`attention_form` once, after the step's first call (which
        traced it): a line through ``log`` and an `observe.events` event
        of that name; nothing where no call was flash's to take."""
        if self._attention_reported:
            return
        self._attention_reported = True
        found = self.attention_form()
        if found is None:
            return
        from tpu_dist.observe import events as _events

        _events.from_env().emit("attention_form", **found)
        log(
            "attention under the partition engine: {form}, "
            "batch and heads over {axes}, {per_device_shape} a device, "
            "{calls} calls, per-device body traced {per_device_traces} "
            "time(s)".format(**found)
        )


def make_partitioned_train_step(
    loss_fn: Callable[..., Any],
    optimizer,
    mesh: Mesh,
    params: Any,
    rules: RuleSet,
    *,
    accum_steps: int = 1,
    donate: bool = True,
    compress=None,
) -> PartitionedTrainStep:
    """ONE train step for every rule set — the engine's whole point.

    ``loss_fn(params, batch, key) -> (loss, aux)`` is the GLOBAL
    computation (mean over the global batch), written as if on one big
    device; XLA's SPMD partitioner derives the per-device program and
    every collective from the shardings:

    - params enter/leave under the param rules;
    - the batch shards its leading axis over ``rules.data_axes``;
    - gradients are constrained to the OPT rules before the update, so
      the optimizer math (and its state) runs sharded — the compiled
      step carries no full-size replicated update op on any set but
      pure dp (tests/test_hlo_structure.py asserts this);
    - ``accum_steps=k`` scans k microbatches with a gradient-sum carry
      (same contract as the strategy builders: one sync per step, mean
      gradient, activations 1/k).

    ``compress`` (a `comm.compress.CompressConfig` or spec string like
    ``"int8"``) swaps the partitioner-derived f32 gradient sync for the
    bucketed quantized wire with two-round error feedback
    (`comm.compress.all_reduce_rows`), INSIDE the same GSPMD program:
    the loss/backward run in a shard_map region manual over the DATA
    axes only (model axes stay auto — XLA still partitions the math
    over tp), each data rank's gradient ships as 1-byte (or bf16)
    bucket chunks through a compressed reduce-scatter + all-gather pair
    per bucket, and the EF residual rides the optimizer-state slot as
    ``{"opt": ..., "ef": {"residual", "err"}}``, sharded by the
    engine's own rules and donated with it.  Model-sharded (tp) leaves
    compress AT THEIR SHARD SHAPE — the wire reduces over the data
    axes; model axes are untouched.  Per-rank loss keys are derived by
    folding the data-axis coordinate into the step key, so dropout
    masks differ across data ranks exactly like the retired strategy
    builders' did.  The only refusal left: a leaf whose single dim is
    sharded over BOTH a data and a model axis (mixed 2-D tuples) cannot
    ride the wire.

    Returns a `PartitionedTrainStep`; its ``step(params, opt_state,
    batch, key) -> (params, opt_state, loss, aux)`` donates params/opt
    state when ``donate``.  The returned ``params``/``opt_state`` are
    freshly placed under the rules (safe to donate immediately)."""
    if accum_steps < 1:
        raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
    from tpu_dist.comm import compress as compress_mod

    ccfg = compress_mod.parse(compress)
    wrap_ef = ccfg is not None and ccfg.error_feedback
    # Opt-state specs from the ABSTRACT init (eval_shape): the full
    # replicated state is never materialized — under an fsdp rule set
    # whose adamw moments only fit sharded, a concrete init here would
    # OOM before the first step.
    opt_template = jax.eval_shape(optimizer.init, params)
    # A user rule matching ZERO leaves (in params AND opt state) is
    # almost always a typo'd pattern whose layer silently fell through
    # to the built-ins — loud at build time (warning + telemetry event)
    # and a `dead-rule` lint finding in `tpu_dist.analysis`.
    dead = dead_user_rules(rules, params, mesh, opt_tree=opt_template)
    if dead:
        import warnings

        from tpu_dist.observe import events as _events

        msg = (
            f"partition rule set {rules.name!r}: user rules matching no "
            f"parameter leaf (dead): {list(dead)}"
        )
        warnings.warn(msg, stacklevel=2)
        _events.from_env().emit("warning", reason=msg, dead_rules=list(dead))
    param_specs = match_partition_rules(rules.param_rules, params, mesh)
    update_specs = match_partition_rules(rules.opt_rules, params, mesh)
    opt_specs = match_partition_rules(rules.opt_rules, opt_template, mesh)

    as_sharding = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    p_sh = jax.tree_util.tree_map(as_sharding, param_specs, is_leaf=is_spec)
    o_sh = jax.tree_util.tree_map(as_sharding, opt_specs, is_leaf=is_spec)
    u_sh = jax.tree_util.tree_map(as_sharding, update_specs, is_leaf=is_spec)
    b_sh = NamedSharding(mesh, rules.batch_spec())

    vg = jax.value_and_grad(loss_fn, has_aux=True)

    def accumulate(params, batch, key):
        def to_micro(a):
            if a.shape[0] % accum_steps:
                raise ValueError(
                    f"global batch {a.shape[0]} not divisible by "
                    f"accum_steps {accum_steps}"
                )
            return a.reshape(
                (accum_steps, a.shape[0] // accum_steps) + a.shape[1:]
            )

        micro = jax.tree.map(to_micro, batch)
        g0 = jax.tree.map(jnp.zeros_like, params)

        def body(carry, xs):
            gacc, lacc = carry
            mb, i = xs
            (loss, aux), g = vg(params, mb, jax.random.fold_in(key, i))
            return (jax.tree.map(jnp.add, gacc, g), lacc + loss), aux

        with jax.named_scope("grad_accum"):
            (gsum, lsum), auxs = jax.lax.scan(
                body, (g0, 0.0), (micro, jnp.arange(accum_steps))
            )
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
        aux = jax.tree.map(
            lambda a: a.mean(0)
            if jnp.issubdtype(a.dtype, jnp.floating)
            else a[-1],
            auxs,
        )
        return grads, lsum / accum_steps, aux

    flat_plan = None
    said = partitioned_over(
        mesh, batch_axes=rules.data_axes, head_axes=rules.model_axes
    )
    if ccfg is None:

        def train_step(params, opt_state, batch, key):
            # XLA partitions this program over the mesh, and only this
            # builder knows that, and by which axes (the compressed
            # path's `shard_map` below says as much by itself)
            with said:
                if accum_steps == 1:
                    (loss, aux), grads = vg(params, batch, key)
                else:
                    grads, loss, aux = accumulate(params, batch, key)
            # The sharded weight update: pin the gradient (same shapes
            # as params) to the UPDATE layout, so the optimizer's
            # elementwise math — and the momenta it reads/writes —
            # partitions with it instead of replicating (arxiv
            # 2004.13336's transformation, expressed as a sharding
            # constraint instead of a rewrite).
            with jax.named_scope("optimizer"):
                grads = jax.lax.with_sharding_constraint(grads, u_sh)
                new_params, new_opt = optimizer.update(
                    params, grads, opt_state
                )
            return new_params, new_opt, loss, aux

        o_sh_step = o_sh
    else:
        # ---- the compressed data-axis wire, inside the GSPMD program.
        # Manual region over the DATA axes only (model axes stay auto):
        # each data rank computes its local-shard gradient, ships it as
        # quantized buckets through `all_reduce_rows`, and hands the
        # data-replicated mean gradient back to the sharded update.
        data_axes = tuple(rules.data_axes)
        model_axes = tuple(rules.model_axes)
        ax = data_axes if len(data_axes) > 1 else data_axes[0]
        n_data = int(np.prod([_axis_size(mesh, a) for a in data_axes]))
        p_leaves, p_treedef = jax.tree_util.tree_flatten(params)
        spec_leaves = p_treedef.flatten_up_to(param_specs)
        # A dim sharded over BOTH a data and a model axis (the 2-D
        # tp×fsdp weight sharding) interleaves model and data shards in
        # one dimension — the flat bucket layout cannot split that into
        # a model-local row matrix.  Refuse loudly, naming the leaves.
        mixed = [
            path
            for (path, _), spec in zip(tree_paths(params), spec_leaves)
            for e in tuple(spec)
            if isinstance(e, tuple)
            and set(e) & set(data_axes)
            and set(e) & set(model_axes)
        ]
        if mixed:
            compress_mod.refuse_model_axes(
                "make_partitioned_train_step(compress=...)",
                model_axes,
                rules=(
                    f"rule set {rules.name!r}: leaves {sorted(set(mixed))} "
                    "shard one dim over model AND data axes (2-D weight "
                    "sharding)"
                ),
                hint="Use a mesh_axes spec whose model and data axes land "
                "on different dims (e.g. dp×tp), or drop compress.",
            )
        # Shapes as the sync region sees them: full along data dims
        # (params are gathered there), 1/|tp| along model-sharded dims.
        local_tmpl = jax.tree_util.tree_unflatten(p_treedef, [
            jax.ShapeDtypeStruct(
                _local_shape(tuple(leaf.shape), spec, model_axes, mesh),
                leaf.dtype,
            )
            for leaf, spec in zip(p_leaves, spec_leaves)
        ])
        flat_plan = compress_mod.FlatPlan(local_tmpl, n_data, ccfg)
        res_spec = compress_mod.engine_residual_spec(data_axes, model_axes)
        res_manual = _strip_spec(res_spec, data_axes)
        g_model_specs = jax.tree_util.tree_unflatten(
            p_treedef, [_strip_spec(s, model_axes) for s in spec_leaves]
        )
        manual_p_specs = jax.tree_util.tree_unflatten(
            p_treedef, [_strip_spec(s, data_axes) for s in spec_leaves]
        )
        # nan_guard-wrapped optimizers advertise current_scale: poison
        # grads on a non-finite LOSS before the sync so the wire's
        # all-finite predicate holds the residual and the guard skips
        # the step — the legacy builders' contract, kept.
        guarded = getattr(optimizer, "current_scale", None) is not None

        def sync_local(grads_local, residual_local):
            """Leaves at MODEL-shard shapes; reduce over data axes."""
            rows = flat_plan.to_rows(grads_local)
            res = residual_local[0] if residual_local is not None else None
            total, new_res, stats = compress_mod.all_reduce_rows(
                rows, res, flat_plan, ax,
                predicate_axes=data_axes + model_axes,
            )
            grads_mean = flat_plan.from_rows(total / n_data)
            err = stats["err"]
            if model_axes:
                err = jax.lax.pmean(err, model_axes)
            return (
                grads_mean,
                new_res[None] if new_res is not None else None,
                err,
            )

        if model_axes:
            m_ax = model_axes if len(model_axes) > 1 else model_axes[0]
            inner_res_spec = P(None, None, m_ax)
            # Nested inside the data-manual region: no mesh argument (the
            # context mesh, already manual over the data axes, is the
            # one), manual over the model axes it adds.
            inner_manual = frozenset(model_axes)

            def sync(grads, residual):
                if wrap_ef:
                    return jax.shard_map(
                        sync_local,
                        in_specs=(g_model_specs, inner_res_spec),
                        out_specs=(g_model_specs, inner_res_spec, P()),
                        axis_names=inner_manual,
                        check_vma=False,
                    )(grads, residual)
                def stateless(g_):
                    out = sync_local(g_, None)
                    return out[0], out[2]

                g, e = jax.shard_map(
                    stateless,
                    in_specs=(g_model_specs,),
                    out_specs=(g_model_specs, P()),
                    axis_names=inner_manual,
                    check_vma=False,
                )(grads)
                return g, None, e
        else:
            sync = sync_local

        def region(params, batch, key, residual):
            # Per-rank keys: the data-axis coordinate folds into the
            # step key, so dropout masks differ across data ranks (the
            # strategy builders' per-rank stream, kept under the
            # engine).
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
            full = jax.tree_util.tree_unflatten(p_treedef, [
                _gather_axes(leaf, spec, data_axes)
                for leaf, spec in zip(
                    p_treedef.flatten_up_to(params), spec_leaves
                )
            ])
            if accum_steps == 1:
                (loss, aux), grads = vg(full, batch, key)
            else:
                grads, loss, aux = accumulate(full, batch, key)
            if guarded:
                from tpu_dist.resilience.guards import _poison

                grads = _poison(grads, ~jnp.isfinite(loss))
            from tpu_dist.parallel.data_parallel import _pmean_float_leaves

            with jax.named_scope("grad_sync"):
                grads, new_res, err = sync(grads, residual)
                loss = jax.lax.pmean(loss, ax)
                aux = _pmean_float_leaves(aux, ax)
            return grads, loss, aux, new_res, err

        # manual over the data axes only; the model axes stay auto
        manual = frozenset(data_axes)
        if wrap_ef:
            mapped = jax.shard_map(
                region,
                mesh=mesh,
                in_specs=(manual_p_specs, rules.batch_spec(), P(), res_manual),
                out_specs=(P(), P(), P(), res_manual, P()),
                check_vma=False,
                axis_names=manual,
            )
        else:
            mapped = jax.shard_map(
                lambda p, b, k: region(p, b, k, None)[:3],
                mesh=mesh,
                in_specs=(manual_p_specs, rules.batch_spec(), P()),
                out_specs=(P(), P(), P()),
                check_vma=False,
                axis_names=manual,
            )

        def train_step(params, opt_state, batch, key):
            inner_opt = opt_state["opt"] if wrap_ef else opt_state
            if wrap_ef:
                grads, loss, aux, new_res, err = mapped(
                    params, batch, key, opt_state["ef"]["residual"]
                )
            else:
                grads, loss, aux = mapped(params, batch, key)
            with jax.named_scope("optimizer"):
                grads = jax.lax.with_sharding_constraint(grads, u_sh)
                new_params, new_opt = optimizer.update(
                    params, grads, inner_opt
                )
            if wrap_ef:
                new_opt = {
                    "opt": new_opt,
                    "ef": {"residual": new_res, "err": err},
                }
            return new_params, new_opt, loss, aux

        if wrap_ef:
            ef_sh = {
                "residual": NamedSharding(mesh, res_spec),
                "err": NamedSharding(mesh, P()),
            }
            o_sh_step = {"opt": o_sh, "ef": ef_sh}
            opt_specs = {
                "opt": opt_specs,
                "ef": {"residual": res_spec, "err": P()},
            }
        else:
            o_sh_step = o_sh

    # jit names the program after this function: `train_step` on the
    # trace's `XLA Modules` line
    step = jax.jit(
        train_step,
        in_shardings=(p_sh, o_sh_step, b_sh, None),
        out_shardings=(p_sh, o_sh_step, None, None),
        donate_argnums=(0, 1) if donate else (),
    )
    # both kept: what the state's placement costs every start (the
    # parameters' trip through the host, the optimizer init's compile)
    with spans.span("partition.place_params", keep=True) as sp:
        placed_params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(np.asarray(a), s), params, p_sh
        )
        sp.attrs["bytes"] = sum(a.nbytes for a in jax.tree.leaves(placed_params))
    # Opt state is born sharded: init compiled with the opt shardings as
    # out-shardings, so each device writes only its own shard (no full
    # host copy, no device->host->device round trip).
    with spans.span("partition.init_opt", keep=True):
        placed_opt = jax.jit(optimizer.init, out_shardings=o_sh)(placed_params)
    if ccfg is not None and wrap_ef:
        placed_opt = {
            "opt": placed_opt,
            "ef": compress_mod.init_engine_ef_state(
                flat_plan, mesh, rules.data_axes, rules.model_axes
            ),
        }
    return PartitionedTrainStep(
        step=step,
        params=placed_params,
        opt_state=placed_opt,
        param_specs=param_specs,
        opt_specs=opt_specs,
        ruleset=rules,
        mesh=mesh,
        dead_rules=dead,
        compress=ccfg,
        flat_plan=flat_plan,
        partitioned=said,
    )
