"""A tiny preset of the whole benchmark for CPU rehearsal: the committed
manifest, data files and readers copied into a temp root, with every size
shrunk.  Same cells, same code; the numbers mean nothing."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

MODEL = {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_positions": 128, "n_ctx": 128,
         "vocab_size": 512,
         # wide enough that two blocks of width 64 outweigh the embedding:
         # at the published 0.02 the tied head just echoes the last token
         "initializer_range": 0.15}
# loose on purpose: float32 against float32 at these sizes reads ~1e-6; the
# limits that matter are the committed ones, set from chip readings
LIMITS = {"train": {"loss_gap": 1e-3, "grad_norm_gap": 1e-2, "delta_norm_gap": 1e-2},
          "serve": {"served_logit_gap": 1e-3}}


def _edit(path: Path, fn) -> None:
    doc = json.loads(path.read_text())
    fn(doc)
    path.write_text(json.dumps(doc, indent=2))


def make_tiny_root(tmp: Path) -> Path:
    root = Path(tmp) / "root"
    root.mkdir()
    shutil.copy(REPO / "BENCHMARK.json", root / "BENCHMARK.json")
    for sub in ("configs", "traffic", "layer_metrics", "families"):
        shutil.copytree(REPO / "chipbench" / sub, root / "chipbench" / sub)

    def config(doc):
        doc.update(MODEL)
        doc["limits"] = LIMITS
        doc["train"].update(batch_tokens=512, micro_batch_rows=4, reference_block_rows=4,
                            env={}, compute_dtype="float32")
        doc["serve"].update(dtype="float32", max_batch=4, block_size=8, num_blocks=64,
                            max_seq=128, prefill_chunk=16, prefill_batch=2, env={})

    for name in ("gpt2-medium", "gpt2-xl"):
        _edit(root / "chipbench/configs" / f"{name}.json", config)
    _edit(root / "chipbench/traffic/pretrain-1024.json", lambda d: d.update(
        seq_len=64, pool_steps=8, trace_seconds=0.3))
    _edit(root / "chipbench/traffic/short-answer-open.json", lambda d: d.update(
        rate_per_s=12.0, drain_s=0.6, check_requests=4, trace_seconds=0.3,
        prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.7, "min": 8, "max": 64},
        output_tokens={"dist": "lognormal", "median": 6, "sigma": 0.5, "min": 4, "max": 12}))
    _edit(root / "chipbench/traffic/long-answer-closed.json", lambda d: d.update(
        clients=4, requests_per_client=6, check_requests=3, trace_seconds=0.3,
        prompt_tokens={"dist": "uniform", "min": 8, "max": 16},
        output_tokens={"dist": "uniform", "min": 16, "max": 48}))
    return root
