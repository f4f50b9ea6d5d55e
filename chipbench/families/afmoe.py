"""Family ``afmoe``: gated grouped-query attention layers of two kinds (a
windowed one with rope, a full one with no positions) between sandwich
norms, over a leading dense feed-forward and sigmoid-routed experts with a
route scale, of which this chip holds a range (``chipbench/families/gpt2.py``'s
docstring lists what a family file offers).

A configuration of this family is the chip's share of a deployment: its
``num_experts`` are the experts HELD here (``held_experts = [lo, hi)`` of the
router's ``router_experts`` outputs), its ``vocab_size`` the slice of the
vocabulary held here, its ``num_hidden_layers`` the layers of this pipeline
stage.  Every count below is of what is held: the parameters a decode step
reads, the operations of the picks that land here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import afmoe_ref as reference

# the `jax.named_scope` names this family's layers add to the program's own
# (`tpu_dist/nn/attention.py`, `serve/paged_kv.py`'s rings, `parallel/moe.py::
# routed_experts`); the full layers keep `attn/*`, the dense first layer `mlp`
SCOPES = (
    "attn/qk_norm", "attn/gate", "swa/ring_rw", "swa/attend",
    "moe/router", "moe/sort", "moe/experts", "moe/combine", "moe/shared",
)
KERNELS = ("paged_attn_decode",)   # the full layer's pool and the rings are read through it
# the configuration's names for its layer kinds -> `hybrid_lm.MIXERS`'
KINDS = {"full_attention": "gated_attention", "sliding_attention": "gated_sliding_attention"}


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def _kv_row(cfg: dict) -> int:
    """Values a token leaves in ONE layer's cache: a key and a value a K/V head."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"]


def _attention_params(cfg: dict) -> int:
    """An attention layer with its gate and its two head norms."""
    D, d = cfg["hidden_size"], cfg["head_dim"]
    return 3 * D * cfg["num_attention_heads"] * d + D * _kv_row(cfg) + 2 * d


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def _routed_params(cfg: dict) -> int:
    """Every routed expert held here, all expert layers."""
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    return layers * cfg["num_experts"] * _expert_params(cfg)


def param_count(cfg: dict) -> int:
    """Parameters HELD here (embedding and head untied, both counted; four
    norms a layer and the last one): what a decode step could read."""
    D, L, lead = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["num_dense_layers"]
    outside = (D * cfg["router_experts"] + cfg["router_experts"]
               + cfg["num_shared_experts"] * _expert_params(cfg))
    return (2 * cfg["vocab_size"] * D + D + L * (_attention_params(cfg) + 4 * D)
            + lead * 3 * D * cfg["intermediate_size"] + (L - lead) * outside + _routed_params(cfg))


def picks_held_per_token(cfg: dict) -> float:
    """Of a token's picks, how many land on the held experts if the router
    spreads them evenly."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["router_experts"]


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations one token's forward pass requires HERE at ``seq_len``: the
    projections; a full layer's attention over the realisable scores, a
    windowed layer's over its window; the router, the picks that land on the
    held experts, the shared expert, the dense layer; the head."""
    D, hd = cfg["hidden_size"], cfg["num_attention_heads"] * cfg["head_dim"]
    seen = (seq_len + 1) / 2
    kinds = cfg["layer_types"]
    attend = 4 * hd * (kinds.count("full_attention") * seen
                       + kinds.count("sliding_attention") * min(seen, cfg["sliding_window"]))
    lead = cfg["num_dense_layers"]
    experts = (2 * D * cfg["router_experts"]
               + (picks_held_per_token(cfg) + cfg["num_shared_experts"]) * 2 * _expert_params(cfg))
    return (len(kinds) * 2 * _attention_params(cfg) + attend + lead * 6 * D * cfg["intermediate_size"]
            + (len(kinds) - lead) * experts + 2 * cfg["vocab_size"] * D)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int) -> int:
    """What a token leaves in the POOLS: a key and a value for every full
    layer (a windowed layer's ring is a state of the slot, not of the token)."""
    return cfg["layer_types"].count("full_attention") * _kv_row(cfg) * bytes_per_value


def decode_required_bytes(cfg: dict, counts: dict, bytes_per_value: int) -> float:
    """The least bytes one decode step has to move: every held weight once
    BUT a routed expert's only where the step gave it a token; a key and a
    value for every position a full layer attends in the pool and for every
    position a windowed layer attends in its ring.  ``counts``: the step's
    ``moe_experts_hit`` (held experts given a token, summed over the expert
    layers), ``attn_rows_attended``, ``swa_rows_attended`` (each summed over
    the busy slots and the layers of its kind), as the program counts them."""
    weights = param_count(cfg) - _routed_params(cfg) + counts["moe_experts_hit"] * _expert_params(cfg)
    return bytes_per_value * (weights + _kv_row(cfg) * (
        counts["attn_rows_attended"] + counts["swa_rows_attended"]))


def attended_row_bytes(cfg: dict, bytes_per_value: int) -> int:
    """What `paged_attn_decode` has to read for one position a query attends."""
    return _kv_row(cfg) * bytes_per_value


def tiny(cfg: dict) -> dict:
    """The rehearsal's size: the dense first layer and a whole period (three
    windowed layers to a full one), eight router outputs of which the first
    four are held, three picks a token, a head size that is not ``hidden /
    heads``; a window (9) smaller than the rehearsal's requests (24-64
    tokens) and a ring (9 - 1 + 16 = 24 rows) that wraps."""
    del cfg
    return {
        "hidden_size": 64, "num_hidden_layers": 5, "num_dense_layers": 1,
        "layer_types": ["sliding_attention", "sliding_attention", "full_attention",
                        "sliding_attention", "sliding_attention"],
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 24, "sliding_window": 9,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "router_experts": 8, "num_experts": 4, "held_experts": [0, 4], "num_experts_per_tok": 3,
        "vocab_size": 512, "max_position_embeddings": 128,
        # at the published 0.02 and these widths a sublayer's output is all
        # but the same whatever the context: the sandwich norm would blow
        # up rounding noise
        "initializer_range": 0.1,
        "bias_calibration": {"sequences": 16, "tokens": 256, "steps": 120},
    }


def to_program(top: dict, layers: list[dict]) -> dict:
    """`reference.init_parts` -> the tree `HybridLM.init` returns."""
    def mixer(m):
        return {"q": {"w": m["wq"]}, "kv": {"w": jnp.concatenate([m["wk"], m["wv"]], axis=1)},
                "gate": {"w": m["wg"]}, "out": {"w": m["wo"]},
                "q_norm": {"scale": m["q_norm"]}, "k_norm": {"scale": m["k_norm"]}}

    def block(b):
        p = {name: {"scale": b[name]} for name in ("ln1", "ln1_out", "ln2", "ln2_out")}
        p["mixer"] = mixer(b["mixer"])
        if "ff_in" in b:
            return {**p, "mlp": {"w_in": b["ff_in"], "w_out": b["ff_out"]}}
        return {**p,
                "moe": {"router": b["router"], "bias": b["router_bias"],
                        "w_in": b["experts_in"], "w_out": b["experts_out"]},
                "shared": {"w_in": b["shared_in"], "w_out": b["shared_out"]}}

    return {"embed": {"table": top["wte"]}, "blocks": [block(b) for b in layers],
            "ln": {"scale": top["lnf"]}, "head": {"table": top["head"].T}}


def to_reference(tree: dict) -> dict:
    """The program's tree under the reference's names."""
    def mixer(m):
        wk, wv = jnp.split(m["kv"]["w"], 2, axis=1)
        return {"wq": m["q"]["w"], "wk": wk, "wv": wv, "wg": m["gate"]["w"], "wo": m["out"]["w"],
                "q_norm": m["q_norm"]["scale"], "k_norm": m["k_norm"]["scale"]}

    def layer(b):
        p = {name: b[name]["scale"] for name in ("ln1", "ln1_out", "ln2", "ln2_out")}
        p["mixer"] = mixer(b["mixer"])
        if "mlp" in b:
            return {**p, "ff_in": b["mlp"]["w_in"], "ff_out": b["mlp"]["w_out"]}
        return {**p, "router": b["moe"]["router"], "router_bias": b["moe"]["bias"],
                "experts_in": b["moe"]["w_in"], "experts_out": b["moe"]["w_out"],
                "shared_in": b["shared"]["w_in"], "shared_out": b["shared"]["w_out"]}

    return {"wte": tree["embed"]["table"], "lnf": tree["ln"]["scale"],
            "head": tree["head"]["table"].T, "layers": [layer(b) for b in tree["blocks"]]}


def make_init(cfg: dict, dtype, *, layout: str):
    """``key -> weights`` in ``layout`` 'program' or 'reference'.  Both run
    ONE jitted `reference.init_parts` (the calibrated bias is then the same
    numbers whichever layout asks) and lay its parts out after it, leaf by
    leaf: a second program over the whole tree would hold the weights twice."""
    parts = jax.jit(lambda key: reference.init_parts(key, cfg, jnp.dtype(dtype)))

    def init(key):
        top, layers = parts(key)
        return to_program(top, layers) if layout == "program" else {**top, "layers": layers}

    return init


def mixer_sizes(cfg: dict) -> dict:
    """By the program's layer kind, what `HybridLM` builds its gated
    grouped-query mixers with; a windowed layer's ring holds the window and
    one prefill chunk."""
    own = dict(heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
               head_dim=cfg["head_dim"])
    return {
        "gated_attention": own,
        "gated_sliding_attention": dict(own, window=cfg["sliding_window"],
                                        chunk=cfg["serve"]["prefill_chunk"]),
    }


def make_lm(cfg: dict, seeded_key, dtype, *, remat: bool = False):
    """The program's `HybridLM` at the configuration's sizes, whose ``init``
    is the benchmark's seeded generator at ``seeded_key``."""
    from tpu_dist.models.hybrid_lm import HybridLM

    del remat   # the family serves only
    reference.sizes(cfg)   # the keys the program computes one form of
    if cfg["rope_theta"] != 10000:
        raise ValueError(f"the program's rope has base 10000, not {cfg['rope_theta']}")
    init = make_init(cfg, dtype, layout="program")

    class Seeded(HybridLM):
        def init(self, key=None, input_shape=None):
            del key, input_shape
            return init(seeded_key), {}

    return Seeded(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=[KINDS[kind] for kind in cfg["layer_types"]], mixers=mixer_sizes(cfg),
        n_experts=cfg["router_experts"], experts_per_token=cfg["num_experts_per_tok"],
        expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
        held_experts=tuple(cfg["held_experts"]), expert_scoring="sigmoid_normalised",
        route_scale=cfg["route_scale"], dense_layers=cfg["num_dense_layers"],
        dense_width=cfg["intermediate_size"], tied_head=False,
        embedding_multiplier=cfg["hidden_size"] ** 0.5, sandwich_norms=True,
        norm_eps=cfg["rms_norm_eps"], max_seq=cfg["max_position_embeddings"],
    )
