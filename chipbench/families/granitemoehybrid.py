"""Family ``granitemoehybrid``: Mamba-2 and attention mixers over routed
experts, of which this chip holds a range (``chipbench/families/gpt2.py``'s
docstring lists what a family file offers).

A configuration of this family is the chip's share of a deployment: its
``num_local_experts`` are the experts HELD here (``held_experts = [lo,
hi)`` of the router's ``router_experts`` outputs), its ``vocab_size`` the
slice of the vocabulary held here, its ``num_hidden_layers`` the layers of
this pipeline stage.  Every count below is of what is held: the parameters
a decode step reads, the operations of the picks that land here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import granitemoehybrid_ref as reference

# the `jax.named_scope` names of `tpu_dist/models/hybrid_lm.py`, `ops/ssm_scan.py`
# and `parallel/moe.py::routed_experts` (the attention layer keeps `attn/*`)
SCOPES = (
    "ssm/in_proj", "ssm/conv", "ssm/scan", "ssm/gate_norm", "ssm/out_proj", "ssm/state_rw",
    "moe/router", "moe/sort", "moe/experts", "moe/combine", "moe/shared",
)
KERNELS = ()   # no Pallas kernel of its own: the scan and the grouped product are XLA's


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def _layers(cfg: dict) -> tuple[int, int]:
    kinds = cfg["layer_types"]
    return kinds.count("mamba"), kinds.count("attention")


def _per_layer_params(cfg: dict) -> tuple[int, int, int]:
    """(a Mamba mixer, an attention mixer, the expert layer as held), each
    with the RMSNorm in front of it."""
    D, sz = cfg["hidden_size"], reference.sizes(cfg)
    nh, K = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
    mamba = (D * (sz["inner"] + sz["channels"] + nh) + sz["inner"] * D
             + sz["channels"] * (K + 1) + 3 * nh + sz["inner"] + D)
    kv = cfg["num_key_value_heads"] * sz["head_dim"]
    attention = 2 * D * D + 2 * D * kv + D
    experts = (D * cfg["router_experts"] + cfg["num_local_experts"] * 3 * D * cfg["intermediate_size"]
               + 3 * D * cfg["shared_intermediate_size"] + D)
    return mamba, attention, experts


def param_count(cfg: dict) -> int:
    """Parameters HELD here (tied head counted once): what a decode step
    reads, so what `decode_hbm_roofline_pct` takes for the weights."""
    mamba, attention, experts = _per_layer_params(cfg)
    n_mamba, n_attention = _layers(cfg)
    D = cfg["hidden_size"]
    return (cfg["vocab_size"] * D + D + n_mamba * mamba + n_attention * attention
            + (n_mamba + n_attention) * experts)


def picks_held_per_token(cfg: dict) -> float:
    """Of a token's picks, how many land on the held experts if the router
    spreads them evenly."""
    return cfg["num_experts_per_tok"] * cfg["num_local_experts"] / cfg["router_experts"]


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations one token's forward pass requires HERE at ``seq_len``:
    the mixers' projections, the recurrence (update and read of the state,
    2 operations an element each), causal attention over the realisable
    scores, the router, the picks that land on the held experts (not all
    ``num_experts_per_tok``), the shared expert and the sliced tied head."""
    D, sz = cfg["hidden_size"], reference.sizes(cfg)
    nh = cfg["mamba_n_heads"]
    n_mamba, n_attention = _layers(cfg)
    kv = cfg["num_key_value_heads"] * sz["head_dim"]
    mamba = (2 * D * (sz["inner"] + sz["channels"] + nh) + 2 * sz["inner"] * D
             + 4 * sz["inner"] * cfg["mamba_d_state"])
    attention = 2 * (2 * D * D + 2 * D * kv) + 4 * D * (seq_len + 1) / 2
    experts = (2 * D * cfg["router_experts"]
               + picks_held_per_token(cfg) * 6 * D * cfg["intermediate_size"]
               + 6 * D * cfg["shared_intermediate_size"])
    return (n_mamba * mamba + n_attention * attention + (n_mamba + n_attention) * experts
            + 2 * cfg["vocab_size"] * D)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int) -> int:
    """Keys and values of the attention layers alone."""
    kv = cfg["num_key_value_heads"] * reference.sizes(cfg)["head_dim"]
    return 2 * _layers(cfg)[1] * kv * bytes_per_value


def state_bytes_per_slot(cfg: dict) -> int:
    """The float32 recurrent state a decode step reads and writes for a
    busy slot: every Mamba layer's scan state and convolution window."""
    sz = reference.sizes(cfg)
    scan = sz["inner"] * cfg["mamba_d_state"]
    window = (cfg["mamba_d_conv"] - 1) * sz["channels"]
    return 4 * _layers(cfg)[0] * (scan + window)


def tiny(cfg: dict) -> dict:
    """The rehearsal's size: both kinds of layer, eight router outputs of
    which the first four are held, three picks a token, two scan chunks to
    a prefill chunk."""
    del cfg
    return {
        "hidden_size": 64, "num_hidden_layers": 3, "layer_types": ["mamba", "attention", "mamba"],
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 8,
        "intermediate_size": 32, "shared_intermediate_size": 64,
        "router_experts": 8, "num_local_experts": 4, "held_experts": [0, 4],
        "num_experts_per_tok": 3, "vocab_size": 512, "initializer_range": 0.1,
    }


def to_program(top: dict, layers: list[dict]) -> dict:
    """`reference.init_parts` -> the tree `HybridLM.init` returns."""
    def mixer(m):
        if "wq" not in m:
            return {**{k: v for k, v in m.items() if k != "norm"}, "norm": {"scale": m["norm"]}}
        return {"q": {"w": m["wq"]}, "kv": {"w": jnp.concatenate([m["wk"], m["wv"]], axis=1)},
                "out": {"w": m["wo"]}}

    return {
        "embed": {"table": top["wte"]},
        "blocks": [{
            "ln1": {"scale": b["ln1"]}, "mixer": mixer(b["mixer"]), "ln2": {"scale": b["ln2"]},
            "moe": {"router": b["router"], "w_in": b["experts_in"], "w_out": b["experts_out"]},
            "shared": {"w_in": b["shared_in"], "w_out": b["shared_out"]},
        } for b in layers],
        "ln": {"scale": top["lnf"]},
    }


def to_reference(tree: dict) -> dict:
    """The program's tree under the reference's names."""
    def mixer(m):
        if "q" not in m:
            return {**{k: v for k, v in m.items() if k != "norm"}, "norm": m["norm"]["scale"]}
        wk, wv = jnp.split(m["kv"]["w"], 2, axis=1)
        return {"wq": m["q"]["w"], "wk": wk, "wv": wv, "wo": m["out"]["w"]}

    return {
        "wte": tree["embed"]["table"], "lnf": tree["ln"]["scale"],
        "layers": [{
            "ln1": b["ln1"]["scale"], "mixer": mixer(b["mixer"]), "ln2": b["ln2"]["scale"],
            "router": b["moe"]["router"], "experts_in": b["moe"]["w_in"],
            "experts_out": b["moe"]["w_out"],
            "shared_in": b["shared"]["w_in"], "shared_out": b["shared"]["w_out"],
        } for b in tree["blocks"]],
    }


def make_init(cfg: dict, dtype, *, layout: str):
    """A jitted ``key -> weights`` in ``layout`` 'program' or 'reference'."""
    def fn(key):
        if layout == "program":
            return to_program(*reference.init_parts(key, cfg, jnp.dtype(dtype)))
        return reference.init(key, cfg, jnp.dtype(dtype))

    return jax.jit(fn)


def make_lm(cfg: dict, seeded_key, dtype, *, remat: bool = False):
    """The program's `HybridLM` at the configuration's sizes, whose ``init``
    is the benchmark's seeded generator at ``seeded_key``."""
    from tpu_dist.models.hybrid_lm import HybridLM

    del remat   # the family serves only
    if cfg["mamba_n_groups"] != 1:
        raise ValueError("the program's scan shares B and C among all heads: one group")
    reference.sizes(cfg)   # the configuration's sizes agree with each other
    init = make_init(cfg, dtype, layout="program")

    class Seeded(HybridLM):
        def init(self, key=None, input_shape=None):
            del key, input_shape
            return init(seeded_key), {}

    return Seeded(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"], layer_types=cfg["layer_types"],
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        ssm_heads=cfg["mamba_n_heads"], ssm_head_dim=cfg["mamba_d_head"],
        ssm_state=cfg["mamba_d_state"], ssm_conv=cfg["mamba_d_conv"],
        ssm_chunk=cfg["mamba_chunk_size"], n_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"], expert_width=cfg["intermediate_size"],
        shared_width=cfg["shared_intermediate_size"], held_experts=tuple(cfg["held_experts"]),
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"], logits_scaling=cfg["logits_scaling"],
        norm_eps=cfg["rms_norm_eps"], max_seq=cfg["max_position_embeddings"],
    )
