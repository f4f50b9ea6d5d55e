"""Collective-plan extraction from compiled XLA programs.

The reference tutorial's whole value was that you could READ the
distributed program — every send/recv of the hand-rolled ring allreduce
is right there in the source.  Our GSPMD programs hide their collectives
inside XLA: the partition engine (`parallel.partition`) emits whatever
wire structure the SPMD partitioner derives, and until now the only way
to see it was ad-hoc regexes over ``compile().as_text()``.

This module makes the compiled wire structure a first-class, comparable
artifact:

- `extract_plan(fn, args, mesh=...)` lowers + compiles a jitted program
  and parses every collective op (all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute, sync or async-start
  form) out of the post-optimization HLO into a `CollectivePlan`:
  operand dtypes, per-participant shapes and payload bytes, and — by
  matching the op's ``replica_groups`` / ``source_target_pairs`` against
  the mesh — the MESH AXES the collective runs over, recovering the
  axis names GSPMD compiled away.
- `diff_plans(a, b)` compares two plans at collective-STRUCTURE
  granularity: XLA is free to lower one logical reduce-scatter as
  ``all-reduce + slice`` (it does, on CPU), and free to combine or split
  per-leaf all-reduces, so the default comparison is over
  ``(kind-class, axes, dtype)`` signatures of the MAJOR collectives
  (kind-class folds all-reduce/reduce-scatter into ``reduce``; minor =
  every operand ≤ `MINOR_ELEMS` elements, i.e. scalar loss/predicate
  reductions and control plumbing).  ``strict=True`` adds per-signature
  op counts and payload bytes — the golden-file gate.
- `save_golden` / `load_golden` / `compare_to_golden` persist a plan's
  aggregated rows as JSON under ``tests/goldens/`` so a PR that changes
  the collective structure of a hot path fails CI with a readable plan
  diff (``make analyze`` / ``make analyze-bless``).

Shapes in a partitioned module are PER-DEVICE shard shapes, so
``Collective.bytes`` is the payload one participant feeds the op — the
honest "what does this op put on the wire" number (topology factors like
the ring's 2(n-1)/n are deliberately not applied; see
`comm.compress.FlatPlan.bytes_on_wire` for those).
"""

from __future__ import annotations

import json
import os
import re
from dataclasses import dataclass, field
from itertools import combinations
from typing import Any, Iterable

import numpy as np

COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# Folding for cross-implementation comparison: XLA lowers a logical
# reduce-scatter as all-reduce + dynamic-slice on some backends, so the
# two are one CLASS for diffing purposes.
KIND_CLASS = {
    "all-reduce": "reduce",
    "reduce-scatter": "reduce",
    "all-gather": "gather",
    "all-to-all": "all-to-all",
    "collective-permute": "permute",
}

# An op every one of whose operands is at most this many elements is
# "minor": scalar loss/aux reductions, all-finite predicates, tiny
# resharding plumbing.  Excluded from default plan signatures.
MINOR_ELEMS = 16

# HLO element type -> itemsize (bytes).
_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e4m3": 1,
    "f8e5m2": 1, "f8e4m3b11fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3fnuz": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def itemsize(dtype: str) -> int:
    """Bytes per element of an HLO element type (unknown types count 4,
    so an exotic dtype inflates rather than hides payload)."""
    return _ITEMSIZE.get(dtype, 4)


@dataclass(frozen=True)
class Collective:
    """One collective op of a compiled program.

    ``axes``: the mesh axes the op communicates over, recovered from its
    replica groups / permute pairs (None when no mesh was supplied or
    the groups match no axis combination — e.g. a sub-ring permute).
    ``dtypes``/``shapes``: per-operand element types and per-participant
    shapes.  ``bytes``: summed per-participant operand payload.
    """

    kind: str
    axes: tuple[str, ...] | None
    dtypes: tuple[str, ...]
    shapes: tuple[tuple[int, ...], ...]
    bytes: int
    elems: int

    @property
    def max_elems(self) -> int:
        """Largest single operand (elements) — the minor-op test."""
        return max(
            (int(np.prod(s)) if s else 1 for s in self.shapes), default=0
        )

    @property
    def minor(self) -> bool:
        return self.max_elems <= MINOR_ELEMS

    @property
    def dtype_key(self) -> str:
        return "+".join(sorted(set(self.dtypes))) or "?"

    def sig(self) -> tuple:
        """Comparison signature: (kind-class, axes, dtype)."""
        return (
            KIND_CLASS.get(self.kind, self.kind),
            self.axes if self.axes is not None else ("?",),
            self.dtype_key,
        )

    def summary(self) -> dict:
        return {
            "kind": self.kind,
            "axes": list(self.axes) if self.axes is not None else None,
            "dtypes": list(self.dtypes),
            "shapes": [list(s) for s in self.shapes],
            "bytes": self.bytes,
            "elems": self.elems,
        }


@dataclass
class CollectivePlan:
    """Every collective of one compiled program, in a canonical order."""

    name: str
    collectives: tuple[Collective, ...]
    mesh_axes: dict[str, int] = field(default_factory=dict)

    def __post_init__(self):
        self.collectives = tuple(
            sorted(
                self.collectives,
                key=lambda c: (
                    c.kind,
                    c.axes if c.axes is not None else ("~",),
                    c.dtype_key,
                    -c.bytes,
                    c.shapes,
                ),
            )
        )

    def __iter__(self):
        return iter(self.collectives)

    def __len__(self) -> int:
        return len(self.collectives)

    def count(self, kind: str | None = None) -> int:
        """Ops of ``kind`` (all collectives when None)."""
        if kind is None:
            return len(self.collectives)
        return sum(1 for c in self.collectives if c.kind == kind)

    def major(self) -> tuple[Collective, ...]:
        return tuple(c for c in self.collectives if not c.minor)

    def total_bytes(self, *, major_only: bool = True) -> int:
        src = self.major() if major_only else self.collectives
        return sum(c.bytes for c in src)

    def signatures(self, *, include_minor: bool = False) -> set:
        """The set of `(kind-class, axes, dtype)` signatures —
        `diff_plans`'s default comparison granularity."""
        return {
            c.sig()
            for c in self.collectives
            if include_minor or not c.minor
        }

    def rows(self) -> list[dict]:
        """Aggregated (kind, axes, dtype) rows — the golden format."""
        agg: dict[tuple, dict] = {}
        for c in self.collectives:
            key = (c.kind, c.axes, c.dtype_key)
            row = agg.setdefault(
                key,
                {
                    "kind": c.kind,
                    "axes": list(c.axes) if c.axes is not None else None,
                    "dtype": c.dtype_key,
                    "count": 0,
                    "bytes": 0,
                    "max_elems": 0,
                },
            )
            row["count"] += 1
            row["bytes"] += c.bytes
            row["max_elems"] = max(row["max_elems"], c.max_elems)
        return sorted(
            agg.values(),
            key=lambda r: (r["kind"], r["axes"] or ["~"], r["dtype"]),
        )

    def summary(self) -> dict:
        return {
            "program": self.name,
            "mesh_axes": dict(self.mesh_axes),
            "n_collectives": len(self.collectives),
            "total_bytes": self.total_bytes(major_only=False),
            "rows": self.rows(),
        }

    def to_json(self) -> str:
        payload = dict(self.summary())
        payload["collectives"] = [c.summary() for c in self.collectives]
        return json.dumps(payload, indent=2, sort_keys=True) + "\n"

    @classmethod
    def from_json(cls, text: str) -> "CollectivePlan":
        payload = json.loads(text)
        return cls(
            name=payload.get("program", ""),
            mesh_axes=payload.get("mesh_axes", {}),
            collectives=tuple(
                Collective(
                    kind=c["kind"],
                    axes=tuple(c["axes"]) if c["axes"] is not None else None,
                    dtypes=tuple(c["dtypes"]),
                    shapes=tuple(tuple(s) for s in c["shapes"]),
                    bytes=int(c["bytes"]),
                    elems=int(c["elems"]),
                )
                for c in payload.get("collectives", [])
            ),
        )


# ----------------------------------------------------------- HLO parsing


_OP_RE = re.compile(
    r"=\s*(?:\([^)]*\)|\S+)\s+"
    rf"({'|'.join(COLLECTIVE_OPS)})(?:-start)?\("
)
_OPERAND_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
# ``%name = <shape> op(...)`` — XLA (jaxlib 0.9) prints operands by NAME
# only (``all-reduce(%param.1)``), so operand shapes come from the
# instruction that defined the name.
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*(\([^)]*\)|\S+)\s")
_NAME_RE = re.compile(r"%[\w.\-]+")
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[\d,{} ]*\}\}|"
    r"\[[\d,]+\]<=\[[\d,]+\](?:T\([\d,]+\))?)"
)
_PAIRS_RE = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")


def _parse_shape(dims: str) -> tuple[int, ...]:
    return tuple(int(d) for d in dims.split(",") if d)


def _parse_replica_groups(text: str) -> tuple[tuple[int, ...], ...]:
    """Both HLO renderings: explicit ``{{0,4},{1,5}}`` lists and iota
    ``[G,S]<=[dims]T(perm)`` form (arange over dims, transposed by perm,
    reshaped to G groups of S)."""
    if text.startswith("{{"):
        return tuple(
            tuple(int(x) for x in g.split(",") if x.strip())
            for g in re.findall(r"\{([\d, ]+)\}", text)
        )
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text)
    if m is None:
        return ()
    gshape = _parse_shape(m.group(1))
    dims = _parse_shape(m.group(2))
    arr = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(3):
        arr = arr.transpose(_parse_shape(m.group(3)))
    return tuple(tuple(int(x) for x in g) for g in arr.reshape(gshape))


class _MeshIndex:
    """Axis lookup tables for one mesh: canonical replica-group sets →
    axis-name tuples, and per-axis ring permute pairs.  Group ids are
    POSITIONS in ``mesh.devices.flat`` order (XLA's device assignment
    for a jit over this mesh), not raw device ids."""

    def __init__(self, mesh):
        names = tuple(str(n) for n in mesh.axis_names)
        shape = tuple(int(s) for s in mesh.devices.shape)
        idx = np.arange(int(np.prod(shape))).reshape(shape)
        self.axes = dict(zip(names, shape))
        self.groups: dict[frozenset, tuple[str, ...]] = {}
        # larger subsets first so a size-1 axis collision resolves to
        # the SMALLEST axis set producing those groups
        for r in range(len(names), 0, -1):
            for subset in combinations(range(len(names)), r):
                moved = np.moveaxis(
                    idx, subset, range(len(shape) - r, len(shape))
                )
                size = int(np.prod([shape[i] for i in subset]))
                groups = moved.reshape(-1, size)
                key = frozenset(
                    frozenset(int(x) for x in g) for g in groups
                )
                self.groups[key] = tuple(names[i] for i in subset)
        self.rings: dict[str, set] = {}
        for i, name in enumerate(names):
            fwd = set(
                zip(
                    (int(x) for x in idx.flatten()),
                    (int(x) for x in np.roll(idx, -1, axis=i).flatten()),
                )
            )
            bwd = {(b, a) for a, b in fwd}
            self.rings[name] = fwd | bwd

    def axes_for_groups(self, groups) -> tuple[str, ...] | None:
        key = frozenset(frozenset(g) for g in groups if g)
        return self.groups.get(key)

    def axes_for_pairs(self, pairs) -> tuple[str, ...] | None:
        pairs = set(pairs)
        if not pairs:
            return None
        for name, ring in self.rings.items():
            if pairs <= ring:
                return (name,)
        return None


def parse_hlo_collectives(
    hlo_text: str, mesh=None
) -> tuple[Collective, ...]:
    """Every collective op of one HLO module text.  Counts the sync form
    and the ``-start`` half of async pairs (never the ``-done`` half)."""
    index = _MeshIndex(mesh) if mesh is not None else None
    out = []
    defs: dict[str, str] = {}  # instruction name -> its shape text
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{"):
            defs = {}  # a new computation: names are scoped to it
        d = _DEF_RE.match(line)
        if d is not None:
            defs[d.group(1)] = d.group(2)
        m = _OP_RE.search(line)
        if m is None:
            continue
        kind = m.group(1)
        operands = line[m.end():]
        operands = operands[: operands.find(")")]
        if not _OPERAND_RE.search(operands):
            operands = " ".join(
                defs.get(name, "") for name in _NAME_RE.findall(operands)
            )
        parsed = [
            (dt, _parse_shape(dims))
            for dt, dims in _OPERAND_RE.findall(operands)
        ]
        if not parsed:
            continue
        axes = None
        if index is not None:
            gm = _GROUPS_RE.search(line)
            pm = _PAIRS_RE.search(line)
            if gm is not None:
                axes = index.axes_for_groups(
                    _parse_replica_groups(gm.group(1))
                )
            elif pm is not None:
                pairs = [
                    tuple(int(x) for x in p.split(","))
                    for p in re.findall(r"\{(\d+,\d+)\}", pm.group(1))
                ]
                axes = index.axes_for_pairs(pairs)
        dtypes = tuple(dt for dt, _ in parsed)
        shapes = tuple(s for _, s in parsed)
        elems = sum(int(np.prod(s)) if s else 1 for s in shapes)
        nbytes = sum(
            (int(np.prod(s)) if s else 1) * itemsize(dt)
            for dt, s in parsed
        )
        out.append(
            Collective(
                kind=kind,
                axes=axes,
                dtypes=dtypes,
                shapes=shapes,
                bytes=nbytes,
                elems=elems,
            )
        )
    return tuple(out)


def compiled_text(fn, args: Iterable) -> str:
    """Post-optimization HLO of a jitted fn on example args (arrays or
    `jax.ShapeDtypeStruct`s — nothing executes).  A plain callable is
    jitted first (NOTE: that outer jit carries no donation, so pass the
    already-jitted step when donation is under test)."""
    if not hasattr(fn, "lower"):
        import jax

        fn = jax.jit(fn)
    return fn.lower(*args).compile().as_text()


def extract_plan(
    fn,
    args: Iterable,
    *,
    mesh=None,
    name: str = "",
    hlo_text: str | None = None,
) -> CollectivePlan:
    """The `CollectivePlan` of one jitted program.

    ``fn``/``args`` are lowered and compiled (pass ``hlo_text`` to reuse
    an existing compile); ``mesh`` enables axis-name recovery from
    replica groups.  Extraction is deterministic — retracing the same
    program yields the identical plan (tested)."""
    text = hlo_text if hlo_text is not None else compiled_text(fn, args)
    axes = {}
    if mesh is not None:
        axes = {
            str(k): int(v)
            for k, v in zip(mesh.axis_names, mesh.devices.shape)
        }
    return CollectivePlan(
        name=name,
        collectives=parse_hlo_collectives(text, mesh),
        mesh_axes=axes,
    )


# ------------------------------------------------------------------ diff


def _rename_axes(plan: CollectivePlan, rename: dict) -> CollectivePlan:
    if not rename:
        return plan
    return CollectivePlan(
        name=plan.name,
        mesh_axes={rename.get(k, k): v for k, v in plan.mesh_axes.items()},
        collectives=tuple(
            Collective(
                kind=c.kind,
                axes=tuple(rename.get(a, a) for a in c.axes)
                if c.axes is not None
                else None,
                dtypes=c.dtypes,
                shapes=c.shapes,
                bytes=c.bytes,
                elems=c.elems,
            )
            for c in plan.collectives
        ),
    )


def _sig_str(sig: tuple) -> str:
    kind, axes, dtype = sig
    return f"{kind} over {'x'.join(axes)} [{dtype}]"


def diff_plans(
    a: CollectivePlan,
    b: CollectivePlan,
    *,
    strict: bool = False,
    include_minor: bool = False,
    rename: dict | None = None,
) -> list[str]:
    """Human-readable differences between two plans (empty list = same
    collective plan).

    Default granularity: the `(kind-class, axes, dtype)` signature SETS
    of the major collectives — robust to XLA's freedom to combine
    per-leaf all-reduces or lower reduce-scatter as all-reduce+slice,
    which is what lets the partition engine's GSPMD program compare
    equal to the hand-written shard_map builders (the pinned
    engine-vs-legacy contract for dp/zero1/fsdp).  ``strict=True`` also
    compares per-signature op counts and payload bytes — the golden
    gate's granularity.  ``rename`` maps axis names of ``b`` onto
    ``a``'s vocabulary (e.g. ``{"data": "dp"}``)."""
    if rename:
        b = _rename_axes(b, rename)
    diffs = []
    sa = a.signatures(include_minor=include_minor)
    sb = b.signatures(include_minor=include_minor)
    for sig in sorted(sa - sb):
        diffs.append(f"only in {a.name or 'a'}: {_sig_str(sig)}")
    for sig in sorted(sb - sa):
        diffs.append(f"only in {b.name or 'b'}: {_sig_str(sig)}")
    if strict:
        def keyed(plan):
            rows = {}
            for c in plan.collectives:
                if not include_minor and c.minor:
                    continue
                k = c.sig()
                cnt, byt = rows.get(k, (0, 0))
                rows[k] = (cnt + 1, byt + c.bytes)
            return rows

        ra, rb = keyed(a), keyed(b)
        for sig in sorted(set(ra) & set(rb)):
            (ca, ba), (cb, bb) = ra[sig], rb[sig]
            if ca != cb:
                diffs.append(
                    f"{_sig_str(sig)}: {ca} ops in {a.name or 'a'} vs "
                    f"{cb} in {b.name or 'b'}"
                )
            if ba != bb:
                diffs.append(
                    f"{_sig_str(sig)}: {ba} payload bytes in "
                    f"{a.name or 'a'} vs {bb} in {b.name or 'b'}"
                )
    return diffs


# --------------------------------------------------------------- goldens


def golden_path(goldens_dir: str, program: str) -> str:
    return os.path.join(goldens_dir, f"{program}.json")


def save_golden(plan: CollectivePlan, goldens_dir: str) -> str:
    """Bless ``plan`` as the golden for its program (returns the path).
    The golden stores the AGGREGATED rows — (kind, axes, dtype, count,
    bytes, max_elems) — not per-op shapes, so a pure leaf-order change
    inside one signature does not churn the file.  The jax version the
    golden was blessed under is recorded: exact counts/bytes are an
    XLA-lowering artifact, so comparisons across versions are reported
    as skew, not failure (see `golden_version_skew`)."""
    import jax

    os.makedirs(goldens_dir, exist_ok=True)
    path = golden_path(goldens_dir, plan.name)
    payload = {
        "program": plan.name,
        "mesh_axes": dict(plan.mesh_axes),
        "jax_version": jax.__version__,
        "rows": plan.rows(),
    }
    with open(path, "w", encoding="utf-8") as fh:
        fh.write(json.dumps(payload, indent=2, sort_keys=True) + "\n")
    return path


def load_golden(goldens_dir: str, program: str) -> dict | None:
    path = golden_path(goldens_dir, program)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def golden_version_skew(golden: dict) -> str | None:
    """The golden's blessed jax version when it differs from the running
    one, else None.  Row-exact counts/bytes are deterministic within one
    jax/XLA version but legitimately shift across versions (combiner and
    async-lowering decisions), so callers report skew instead of failing
    the gate — and re-bless under the new version."""
    import jax

    blessed = golden.get("jax_version")
    if blessed is not None and blessed != jax.__version__:
        return str(blessed)
    return None


def compare_to_golden(plan: CollectivePlan, golden: dict) -> list[str]:
    """Differences between a live plan and its blessed golden (empty =
    pass).  Row-exact: kind (NOT kind-class), axes, dtype, op count and
    payload bytes must all match — any change to a hot path's collective
    structure fails with the offending row named."""
    diffs = []
    if dict(plan.mesh_axes) != dict(golden.get("mesh_axes", {})):
        diffs.append(
            f"mesh axes changed: {golden.get('mesh_axes')} -> "
            f"{dict(plan.mesh_axes)}"
        )

    def key(row):
        axes = row["axes"]
        return (row["kind"], tuple(axes) if axes is not None else None,
                row["dtype"])

    live = {key(r): r for r in plan.rows()}
    gold = {key(r): r for r in golden.get("rows", [])}
    for k in sorted(set(gold) - set(live), key=repr):
        r = gold[k]
        diffs.append(
            f"collective gone: {r['kind']} over "
            f"{r['axes']} [{r['dtype']}] x{r['count']}"
        )
    for k in sorted(set(live) - set(gold), key=repr):
        r = live[k]
        diffs.append(
            f"new collective: {r['kind']} over "
            f"{r['axes']} [{r['dtype']}] x{r['count']} "
            f"({r['bytes']} bytes)"
        )
    for k in sorted(set(live) & set(gold), key=repr):
        lr, gr = live[k], gold[k]
        for fieldname in ("count", "bytes", "max_elems"):
            if gr.get(fieldname) is not None and lr[fieldname] != gr[fieldname]:
                diffs.append(
                    f"{lr['kind']} over {lr['axes']} [{lr['dtype']}]: "
                    f"{fieldname} {gr[fieldname]} -> {lr[fieldname]}"
                )
    return diffs
