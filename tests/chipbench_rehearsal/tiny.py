"""A tiny preset of the whole benchmark for CPU rehearsal: the committed
manifest, data files and readers copied into a temp root, with every size
shrunk.  Same cells, same code; the numbers mean nothing.

Nothing here knows a configuration, a family or a traffic file by name: a
configuration is shrunk by its family file's ``tiny(cfg)`` (its own key
names) over the common engine and trainer sizes below, a traffic file by
its ``kind``.  So a cell a later PR adds as files rehearses without an
edit here, and one whose family offers no ``tiny``, or is still above
`MAX_PARAMS` after shrinking, fails at once with the file's name: never a
run at published widths on the CPU.
"""

from __future__ import annotations

import importlib
import json
import shutil
from pathlib import Path

from chipbench import scopes
from chipbench.harness import KINDS
from chipbench.manifest import Cell, Manifest

REPO = Path(__file__).resolve().parents[2]
MAX_PARAMS = 5_000_000

# the sections' common sizes; a family's ``tiny`` may override any of them
SECTIONS = {
    "train": dict(batch_tokens=512, micro_batch_rows=4, reference_block_rows=4, env={},
                  compute_dtype="float32"),
    "serve": dict(dtype="float32", max_batch=4, block_size=8, num_blocks=64, max_seq=128,
                  prefill_chunk=16, prefill_batch=2, env={}),
}
# loose on purpose: float32 against float32 at these sizes reads ~1e-6; the
# limits that matter are the committed ones, set from chip readings
LIMITS = {"train": {"loss_gap": 1e-3, "grad_norm_gap": 1e-2, "delta_norm_gap": 1e-2},
          "serve": {"served_logit_gap": 1e-3}}
# traffic by kind; lengths are clipped to the smallest tiny ``serve.max_seq``
TRAFFIC = {
    "train-tokens": dict(seq_len=64, pool_steps=8, trace_seconds=0.3),
    "serve-open": dict(
        rate_per_s=12.0, drain_s=0.6, check_requests=4, trace_seconds=0.3,
        prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.7, "min": 8, "max": 64},
        output_tokens={"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 4, "max": 12}),
    "serve-closed": dict(
        clients=4, requests_per_client=6, check_requests=3, trace_seconds=0.3,
        prompt_tokens={"dist": "uniform", "min": 8, "max": 16},
        output_tokens={"dist": "uniform", "min": 16, "max": 48}),
}


def shrink_config(doc: dict, family, where: str) -> None:
    """``doc`` at its family's rehearsal size, in place."""
    if not callable(getattr(family, "tiny", None)):
        raise ValueError(
            f"{where}: its family file ({family.__file__}) offers no tiny(cfg), so the "
            "rehearsal would run it at published widths on the CPU")
    over = dict(family.tiny(doc))
    for section, common in SECTIONS.items():
        own = over.pop(section, {})
        if section in doc:   # a configuration may lack `train` or `serve`
            doc[section].update({**common, **own})
    limits = over.pop("limits", {})
    doc["limits"] = {k: {**v, **limits.get(k, {})} for k, v in LIMITS.items()}
    doc.update(over)
    params = family.param_count(doc)
    if params > MAX_PARAMS:
        raise ValueError(
            f"{where}: {params:,} parameters after {family.__file__}'s tiny(cfg); the "
            f"rehearsal runs at most {MAX_PARAMS:,}")


def _clip(dist: dict, most: int) -> dict:
    return {k: min(v, most) if k in ("min", "max", "median", "value") else v
            for k, v in dist.items()}


def shrink_traffic(doc: dict, max_seq: int, where: str) -> None:
    if doc.get("kind") not in TRAFFIC:
        raise ValueError(f"{where}: no tiny preset for traffic kind {doc.get('kind')!r} "
                         f"(known: {sorted(TRAFFIC)})")
    doc.update(TRAFFIC[doc["kind"]])
    # a prompt and its answer fit a slot: half of it each at most
    for key in ("prompt_tokens", "output_tokens"):
        if key in doc:
            doc[key] = _clip(doc[key], max_seq // 2)


def make_tiny_root(tmp: Path, source: Path = REPO) -> Path:
    """``source``'s benchmark (the repo's, or a root a test has added files
    to) copied under ``tmp`` and shrunk."""
    root = Path(tmp) / "root"
    root.mkdir()
    shutil.copy(source / "BENCHMARK.json", root / "BENCHMARK.json")
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(source / "chipbench" / sub, root / "chipbench" / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    manifest = Manifest(root)
    max_seq = SECTIONS["serve"]["max_seq"]
    for path in sorted((root / "chipbench/configs").glob("*.json")):
        doc = json.loads(path.read_text())
        shrink_config(doc, manifest.family(doc["family"]), path.name)
        path.write_text(json.dumps(doc, indent=2))
        max_seq = min(max_seq, doc.get("serve", {}).get("max_seq", max_seq))
    for path in sorted((root / "chipbench/traffic").glob("*.json")):
        doc = json.loads(path.read_text())
        shrink_traffic(doc, max_seq, path.name)
        path.write_text(json.dumps(doc, indent=2))
    return root


def made_trace(cell: Cell) -> tuple[dict, dict]:
    """What `harness.TraceSlice.reduce` hands back, made from the vocabulary
    and not recorded: every program the cell's kind runs, each with every
    scope of `chipbench.scopes.vocabulary` (the cell's family's among them)
    in it, and every kernel of that vocabulary, holds time.  A reader of a
    scope or kernel a later family file brings finds it here."""
    programs = importlib.import_module(KINDS[cell.traffic["kind"]]).PROGRAMS
    vocab, kernels = scopes.vocabulary(cell.family)
    runs, run_s, kernel_share = 5, 0.04, 0.25
    per_scope = run_s * runs * (1 - kernel_share) / len(vocab)
    by_scope = [[p, scope, which, per_scope / 2]
                for p in programs for scope in vocab for which in ("fwd", "bwd")]
    per_kernel = run_s * runs * kernel_share / len(kernels)
    total = run_s * runs * len(programs)
    table = {
        "chip": "/device:TPU:0",
        "programs": [[p, run_s * runs] for p in programs],
        "program_runs": {p: [run_s] * runs for p in programs},
        "by_scope": by_scope + [[programs[0], scopes.UNSCOPED, "fwd",
                                 total - sum(s for *_, s in by_scope)]],
        "total_self_s": total,
        "scoped_share_pct": 100.0 * sum(s for *_, s in by_scope) / total,
        "fallback_share_pct": 0.0,
        "by_op_scope": [["fusion", scope, scopes.OWN, per_scope * len(programs)]
                        for scope in vocab],
        "kernels": [[k, per_kernel, 4 * runs] for k in kernels],
        "collectives_by_scope": [],
        "idle_gaps": [["tpu_dist/engine.decode_wait", 0.06], ["chipbench/train_step", 0.04]],
        "idle_gaps_at_bounds": [], "window_s": total + 0.1,
        "device_ahead_ms": 0.0, "device_ahead_bounds_ms": None,
    }
    reduced = {"busy_s": total, "window_s": total + 0.1, "chips": cell.chips,
               "device_ops": [["fusion", total]], "collective_share_pct": 12.5,
               "idle_gaps": [["train_step", 0.1]]}
    return reduced, table
