"""Family ``dots3_note``: latent-attention layers of two kinds (one that
selects its keys by a learned index, one windowed) over a leading dense
feed-forward and sigmoid-routed experts, of which this chip holds a range
(``chipbench/families/gpt2.py``'s docstring lists what a family file offers).

A configuration of this family is the chip's share of a deployment: its
``n_routed_experts`` are the experts HELD here (``held_experts = [lo, hi)``
of the router's ``router_experts`` outputs), its ``vocab_size`` the slice of
the vocabulary held here, its ``num_hidden_layers`` the layers of this
pipeline stage.  Every count below is of what is held: the parameters a
decode step reads, the operations of the picks that land here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import dots3_note_ref as reference

# the `jax.named_scope` names of `tpu_dist/nn/latent_attention.py`,
# `serve/paged_kv.py`'s latent layers and `parallel/moe.py::routed_experts`
# (the dense first layer keeps `mlp`)
SCOPES = (
    "mla/q", "mla/kv", "mla/cache_write", "dsa/index", "dsa/topk", "dsa/gather", "mla/attend",
    "mla/out", "swa/ring_rw", "swa/attend",
    "moe/router", "moe/sort", "moe/experts", "moe/combine", "moe/shared",
)
KERNELS = ()   # no Pallas kernel: the selection, the row fetch and the grouped product are XLA's


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def _layers(cfg: dict) -> tuple[int, int]:
    kinds = cfg["layer_types"]
    return kinds.count("full_attention"), kinds.count("sliding_attention")


def _mixer_params(cfg: dict, kind: str) -> int:
    """A latent-attention layer of ``kind`` with its two latent norms, and
    for a layer that selects, its indexer with its LayerNorm."""
    D, z = cfg["hidden_size"], reference.sizes(cfg)[kind]
    H = z["heads"]
    own = (D * z["q_rank"] + z["q_rank"] * H * (z["nope"] + z["rope"])
           + D * (z["kv_rank"] + z["rope"]) + z["kv_rank"] * H * (z["nope"] + z["v"])
           + H * z["v"] * D + D * H + z["q_rank"] + z["kv_rank"])
    if kind == "full_attention":
        ih, idim = cfg["index_n_heads"], cfg["index_head_dim"]
        own += z["q_rank"] * ih * idim + D * idim + D * ih + 2 * idim
    return own


def _ff_params(cfg: dict) -> tuple[int, int]:
    """(the dense feed-forward, the expert layer as held: router and its
    bias, the held experts, the shared ones)."""
    D, W = cfg["hidden_size"], cfg["moe_intermediate_size"]
    experts = (D * cfg["router_experts"] + cfg["router_experts"]
               + (cfg["n_routed_experts"] + cfg["n_shared_experts"]) * 3 * D * W)
    return 3 * D * cfg["intermediate_size"], experts


def param_count(cfg: dict) -> int:
    """Parameters HELD here (embedding and head untied, both counted): what
    a decode step reads."""
    D, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    dense, experts = _ff_params(cfg)
    lead = cfg["first_k_dense_replace"]
    mixers = sum(_mixer_params(cfg, kind) for kind in cfg["layer_types"])
    return 2 * cfg["vocab_size"] * D + D + mixers + 2 * L * D + lead * dense + (L - lead) * experts


def picks_held_per_token(cfg: dict) -> float:
    """Of a token's picks, how many land on the held experts if the router
    spreads them evenly."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / cfg["router_experts"]


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations one token's forward pass requires HERE at ``seq_len``, in
    the absorbed form: the projections; a selecting layer's index scores
    over the realisable keys and its attention over the rows it selects; a
    windowed layer's attention over its window; the router, the picks that
    land on the held experts, the shared expert, the dense layer; the head."""
    D, sz = cfg["hidden_size"], reference.sizes(cfg)
    seen = (seq_len + 1) / 2

    def attend(z, rows):   # q' . row and p . c_kv, a head
        return 2 * z["heads"] * rows * (2 * z["kv_rank"] + z["rope"])

    full, swa = sz["full_attention"], sz["sliding_attention"]
    index = 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * seen
    n_full, n_swa = _layers(cfg)
    mixers = (n_full * (2 * _mixer_params(cfg, "full_attention") + index
                        + attend(full, min(seen, cfg["index_topk"])))
              + n_swa * (2 * _mixer_params(cfg, "sliding_attention")
                         + attend(swa, min(seen, cfg["sliding_window_size"]))))
    W = cfg["moe_intermediate_size"]
    experts = (2 * D * cfg["router_experts"]
               + (picks_held_per_token(cfg) + cfg["n_shared_experts"]) * 6 * D * W)
    lead = cfg["first_k_dense_replace"]
    return (mixers + lead * 6 * D * cfg["intermediate_size"]
            + (cfg["num_hidden_layers"] - lead) * experts + 2 * cfg["vocab_size"] * D)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int) -> int:
    """What a token leaves in the POOLS: a latent row and an index key for
    every selecting layer (a windowed layer's ring is a state of the slot,
    not of the token)."""
    full = reference.sizes(cfg)["full_attention"]
    row = full["kv_rank"] + full["rope"]
    return _layers(cfg)[0] * (row + cfg["index_head_dim"]) * bytes_per_value


def decode_required_bytes(cfg: dict, counts: dict, bytes_per_value: int) -> float:
    """The least bytes one decode step has to move: every held weight once
    BUT a routed expert's only where the step gave it a token (an expert
    no token picked is not read); an index key for every position a
    selecting layer scores; a latent row for every row it then selects; a
    ring row for every position a windowed layer attends.  ``counts``: the
    step's ``moe_experts_hit`` (held experts given a token, summed over the
    expert layers), ``dsa_keys_scored``, ``dsa_rows_selected``,
    ``swa_rows_attended`` (each summed over the busy slots and the layers
    of its kind), as the program counts them."""
    sz = reference.sizes(cfg)
    row = lambda z: (z["kv_rank"] + z["rope"]) * bytes_per_value  # noqa: E731
    expert = 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    held = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) * cfg["n_routed_experts"]
    weights = param_count(cfg) - (held - counts["moe_experts_hit"]) * expert
    return (weights * bytes_per_value
            + cfg["index_head_dim"] * bytes_per_value * counts["dsa_keys_scored"]
            + row(sz["full_attention"]) * counts["dsa_rows_selected"]
            + row(sz["sliding_attention"]) * counts["swa_rows_attended"])


def tiny(cfg: dict) -> dict:
    """The rehearsal's size: the dense first layer, both kinds of layer,
    eight router outputs of which the first four are held, three picks a
    token; a selection (8) and a window (9) smaller than the rehearsal's
    requests (24-64 tokens), and a ring (9 - 1 + 16 = 24 rows) that wraps."""
    del cfg
    return {
        "hidden_size": 64, "num_hidden_layers": 4, "first_k_dense_replace": 1,
        "layer_types": ["full_attention", "sliding_attention", "sliding_attention", "full_attention"],
        "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2, "swa_q_lora_rank": 32,
        "swa_kv_lora_rank": 32, "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
        "swa_v_head_dim": 16, "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
        "sliding_window_size": 9, "intermediate_size": 128, "moe_intermediate_size": 32,
        "router_experts": 8, "n_routed_experts": 4, "held_experts": [0, 4],
        "num_experts_per_tok": 3, "vocab_size": 512, "max_position_embeddings": 128,
        # at the published 0.02 and these widths every layer adds next to
        # nothing and the head sees the same row whatever the context
        "initializer_range": 0.1, "query_initializer_range": 0.1,
    }


def to_program(top: dict, layers: list[dict], cfg: dict) -> dict:
    """`reference.init_parts` -> the tree `HybridLM.init` returns."""
    def mixer(m, z):
        p = {name: m[name] for name in ("w_dq", "w_dkv", "w_gate")}
        # as `nn.LatentAttention` keeps them: what multiplies c_q outputs by
        # latent, W_ukv by head in its two parts
        kv = m["w_ukv"].reshape(z["kv_rank"], z["heads"], z["nope"] + z["v"])
        p.update(w_uq=m["w_uq"].T, w_uk=kv[..., :z["nope"]].transpose(1, 2, 0),
                 w_uv=kv[..., z["nope"]:].transpose(1, 0, 2), w_out=m["w_o"],
                 q_norm={"scale": m["q_norm"]}, kv_norm={"scale": m["kv_norm"]})
        if "w_iq" in m:
            p.update(index_wq=m["w_iq"].T, index_wk=m["w_ik"], index_ww=m["w_iw"],
                     index_norm={"scale": m["ik_gain"], "bias": m["ik_bias"]})
        return p

    sz = reference.sizes(cfg)

    def block(b, kind):
        p = {"ln1": {"scale": b["ln1"]}, "mixer": mixer(b["mixer"], sz[kind]),
             "ln2": {"scale": b["ln2"]}}
        if "ff_in" in b:
            return {**p, "mlp": {"w_in": b["ff_in"], "w_out": b["ff_out"]}}
        return {**p,
                "moe": {"router": b["router"], "bias": b["router_bias"],
                        "w_in": b["experts_in"], "w_out": b["experts_out"]},
                "shared": {"w_in": b["shared_in"], "w_out": b["shared_out"]}}

    return {"embed": {"table": top["wte"]},
            "blocks": [block(b, kind) for b, kind in zip(layers, cfg["layer_types"])],
            "ln": {"scale": top["lnf"]}, "head": {"table": top["head"].T}}


def to_reference(tree: dict) -> dict:
    """The program's tree under the reference's names."""
    def mixer(m):
        p = {name: m[name] for name in ("w_dq", "w_dkv", "w_gate")}
        p["w_uq"] = m["w_uq"].T
        kv = jnp.concatenate([m["w_uk"].transpose(2, 0, 1), m["w_uv"].transpose(1, 0, 2)], axis=-1)
        p.update(w_ukv=kv.reshape(kv.shape[0], -1))
        p.update(w_o=m["w_out"], q_norm=m["q_norm"]["scale"], kv_norm=m["kv_norm"]["scale"])
        if "index_wq" in m:
            p.update(w_iq=m["index_wq"].T, w_ik=m["index_wk"], w_iw=m["index_ww"],
                     ik_gain=m["index_norm"]["scale"], ik_bias=m["index_norm"]["bias"])
        return p

    def layer(b):
        p = {"ln1": b["ln1"]["scale"], "mixer": mixer(b["mixer"]), "ln2": b["ln2"]["scale"]}
        if "mlp" in b:
            return {**p, "ff_in": b["mlp"]["w_in"], "ff_out": b["mlp"]["w_out"]}
        return {**p, "router": b["moe"]["router"], "router_bias": b["moe"]["bias"],
                "experts_in": b["moe"]["w_in"], "experts_out": b["moe"]["w_out"],
                "shared_in": b["shared"]["w_in"], "shared_out": b["shared"]["w_out"]}

    return {"wte": tree["embed"]["table"], "lnf": tree["ln"]["scale"],
            "head": tree["head"]["table"].T, "layers": [layer(b) for b in tree["blocks"]]}


def make_init(cfg: dict, dtype, *, layout: str):
    """A jitted ``key -> weights`` in ``layout`` 'program' or 'reference'."""
    def fn(key):
        if layout == "program":
            return to_program(*reference.init_parts(key, cfg, jnp.dtype(dtype)), cfg)
        return reference.init(key, cfg, jnp.dtype(dtype))

    return jax.jit(fn)


def mixer_sizes(cfg: dict) -> dict:
    """By layer kind, what `HybridLM` builds its latent mixers with; a
    windowed layer's ring holds the window and one prefill chunk."""
    def of(kind):
        z = reference.sizes(cfg)[kind]
        return dict(heads=z["heads"], q_rank=z["q_rank"], kv_rank=z["kv_rank"],
                    nope_dim=z["nope"], rope_dim=z["rope"], v_dim=z["v"], rope_base=z["base"])

    return {
        "full_attention": dict(of("full_attention"), index_heads=cfg["index_n_heads"],
                               index_dim=cfg["index_head_dim"], index_topk=cfg["index_topk"]),
        "sliding_attention": dict(of("sliding_attention"), window=cfg["sliding_window_size"],
                                  chunk=cfg["serve"]["prefill_chunk"]),
    }


def make_lm(cfg: dict, seeded_key, dtype, *, remat: bool = False):
    """The program's `HybridLM` at the configuration's sizes, whose ``init``
    is the benchmark's seeded generator at ``seeded_key``."""
    from tpu_dist.models.hybrid_lm import HybridLM

    del remat   # the family serves only
    told = (cfg["attention_gate_type"], cfg["swa_attention_gate_type"], cfg["scoring_func"],
            cfg["topk_method"], cfg["norm_topk_prob"], cfg["tie_word_embeddings"],
            cfg["n_shared_experts"], cfg["moe_layer_freq"], cfg["rope_scaling"],
            cfg["routed_scaling_factor"], cfg["apply_mla_qkv_lora_rescale"])
    if told != ("headwise", "headwise", "sigmoid", "noaux_tc", True, False, 1, 1, None, 1, True):
        raise ValueError(f"the program computes one form of these keys, not {told}")
    init = make_init(cfg, dtype, layout="program")

    class Seeded(HybridLM):
        def init(self, key=None, input_shape=None):
            del key, input_shape
            return init(seeded_key), {}

    return Seeded(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"], layer_types=cfg["layer_types"],
        mixers=mixer_sizes(cfg), n_experts=cfg["router_experts"],
        experts_per_token=cfg["num_experts_per_tok"], expert_width=cfg["moe_intermediate_size"],
        shared_width=cfg["moe_intermediate_size"], held_experts=tuple(cfg["held_experts"]),
        expert_scoring="sigmoid_normalised", dense_layers=cfg["first_k_dense_replace"],
        dense_width=cfg["intermediate_size"], tied_head=False, norm_eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_position_embeddings"],
    )
