"""Family ``longcat_flash``: a layer of two latent-attention sublayers (every
causal row attended: no window, no selection, no gate) and two dense
feed-forwards, with ONE routed mixture that leaves after the first
sublayer's attention and rejoins at the layer's end; a softmax router over
the experts that have weights AND ``zero_expert_num`` that have none
(``chipbench/families/gpt2.py``'s docstring lists what a family file offers).

A configuration of this family is the chip's share of a deployment: its
``n_routed_experts`` are the experts HELD here (``held_experts = [lo, hi)``
of the ``router_experts`` that have weights; the router keeps all its
``router_experts + zero_expert_num`` outputs), its ``vocab_size`` the slice of
the vocabulary held here, its ``num_layers`` the layers of this pipeline
stage.  The zero experts hold nothing, so every chip computes them for its
own tokens: all of them are here.  Every count below is of what is held: the
parameters a decode step reads, the operations of the picks that land here.

To the program a layer is ``SPAN`` sublayers of kind ``latent_attention``
(`HybridLM(shortcut=SPAN)`): ``mixer -> dense feed-forward`` each, the first
launching the routed branch, the last joining it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import longcat_flash_ref as reference

# the `jax.named_scope` names of `tpu_dist/nn/latent_attention.py`,
# `serve/paged_kv.py`'s whole-context latent layer, `parallel/moe.py::
# routed_experts` and `models/hybrid_lm.py`'s join (the dense feed-forwards keep `mlp`)
SCOPES = (
    "mla/q", "mla/kv", "mla/cache_write", "mla/attend", "mla/out",
    "moe/router", "moe/sort", "moe/experts", "moe/combine", "moe/zero", "moe/join",
)
KERNELS = ("paged_latent_decode",)   # decode's read of the latent pool, where it lies
SPAN = 2          # sublayers a layer has, and a routed branch spans
LANES = 128       # the pool keeps a latent row as whole tiles of so many values


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def _row(cfg: dict) -> int:
    """Values a token leaves in ONE sublayer's pool."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def _sublayers(cfg: dict) -> int:
    return SPAN * cfg["num_layers"]


def _attention_params(cfg: dict) -> int:
    """A latent-attention sublayer with its two latent norms."""
    D, H, qr, kr = (cfg[k] for k in ("hidden_size", "num_attention_heads", "q_lora_rank",
                                     "kv_lora_rank"))
    dn, dr, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (D * qr + qr * H * (dn + dr) + D * (kr + dr) + kr * H * (dn + dv) + H * dv * D
            + qr + kr)


def _expert_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def _routed_params(cfg: dict) -> int:
    """Every routed expert held here, all layers."""
    return cfg["num_layers"] * cfg["n_routed_experts"] * _expert_params(cfg)


def param_count(cfg: dict) -> int:
    """Parameters HELD here (embedding and head untied, both counted; two
    attention sublayers, two dense feed-forwards, four norms, the router
    with its selection bias and the held experts a layer; the last norm):
    what a decode step could read."""
    D, outputs = cfg["hidden_size"], cfg["router_experts"] + cfg["zero_expert_num"]
    sublayer = _attention_params(cfg) + 3 * D * cfg["ffn_hidden_size"] + 2 * D
    return (2 * cfg["vocab_size"] * D + D + _sublayers(cfg) * sublayer
            + cfg["num_layers"] * (D * outputs + outputs) + _routed_params(cfg))


def picks_held_per_token(cfg: dict) -> float:
    """Of a token's picks, how many land on the held experts if the router
    spreads them evenly over ALL its outputs."""
    outputs = cfg["router_experts"] + cfg["zero_expert_num"]
    return cfg["moe_topk"] * cfg["n_routed_experts"] / outputs


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations one token's forward pass requires HERE at ``seq_len``, in
    the absorbed form: the projections; each sublayer's attention over the
    realisable rows (``q' . row`` and ``p . c_kv``, a head); the dense
    feed-forwards; the router, the picks that land on the held experts and,
    for the picks on zero experts, a multiply-add a value; the head."""
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    seen = (seq_len + 1) / 2
    attend = 2 * H * seen * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    outputs = cfg["router_experts"] + cfg["zero_expert_num"]
    zero = 2 * D * cfg["moe_topk"] * cfg["zero_expert_num"] / outputs
    routed = 2 * D * outputs + picks_held_per_token(cfg) * 2 * _expert_params(cfg) + zero
    return (_sublayers(cfg) * (2 * _attention_params(cfg) + attend + 6 * D * cfg["ffn_hidden_size"])
            + cfg["num_layers"] * routed + 2 * cfg["vocab_size"] * D)


def kv_bytes_per_token(cfg: dict, bytes_per_value: int) -> int:
    """What a token leaves in the POOLS: a latent row for every sublayer."""
    return _sublayers(cfg) * _row(cfg) * bytes_per_value


def attended_row_bytes(cfg: dict, bytes_per_value: int) -> int:
    """What `paged_latent_decode` has to read for one position a query
    attends: the row as the pool keeps it, whole tiles of `LANES` values,
    ONCE (it is the key and, in its first lanes, the value)."""
    return -(-_row(cfg) // LANES) * LANES * bytes_per_value


def decode_required_bytes(cfg: dict, counts: dict, bytes_per_value: int) -> float:
    """The least bytes one decode step has to move: every held weight once
    BUT a routed expert's only where the step gave it a token; a latent row
    for every position a sublayer attends.  ``counts``: the step's
    ``moe_experts_hit`` (held experts given a token, summed over the layers)
    and ``mla_rows_attended`` (summed over the busy slots and the
    sublayers), as the program counts them."""
    weights = param_count(cfg) - _routed_params(cfg) + counts["moe_experts_hit"] * _expert_params(cfg)
    return (bytes_per_value * weights
            + attended_row_bytes(cfg, bytes_per_value) * counts["mla_rows_attended"])


def tiny(cfg: dict) -> dict:
    """The rehearsal's size: two layers (four sublayers, two routed
    branches), twelve router outputs of which eight have weights, the first
    four of those held, and four have none; three picks a token."""
    del cfg
    return {
        "hidden_size": 64, "num_layers": 2, "num_attention_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "ffn_hidden_size": 128, "expert_ffn_hidden_size": 32,
        "router_experts": 8, "zero_expert_num": 4, "n_routed_experts": 4, "held_experts": [0, 4],
        "moe_topk": 3, "vocab_size": 512, "max_position_embeddings": 128,
        # at the published 0.02 and these widths every sublayer adds next to
        # nothing and the head sees the same row whatever the context
        "initializer_range": 0.1,
        "bias_calibration": {"sequences": 8, "tokens": 256, "steps": 120, "first_step": 0.05,
                             "last_step": 5e-4},
    }


def _mixer_to_program(m: dict, cfg: dict) -> dict:
    """As `nn.LatentAttention` keeps them: what multiplies c_q outputs by
    latent, W_ukv by head in its two parts; no gate."""
    H, dn, dv = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    kv = m["w_ukv"].reshape(cfg["kv_lora_rank"], H, dn + dv)
    return {"w_dq": m["w_dq"], "w_dkv": m["w_dkv"], "w_uq": m["w_uq"].T,
            "w_uk": kv[..., :dn].transpose(1, 2, 0), "w_uv": kv[..., dn:].transpose(1, 0, 2),
            "w_out": m["w_o"], "q_norm": {"scale": m["q_norm"]}, "kv_norm": {"scale": m["kv_norm"]}}


def to_program(top: dict, layers: list[dict], cfg: dict) -> dict:
    """`reference.init_parts` -> the tree `HybridLM.init` returns: a block a
    sublayer, the first of each layer with the experts beside its dense
    feed-forward."""
    blocks = []
    for lp in layers:
        for at, sub in enumerate(lp["sub"]):
            p = {"ln1": {"scale": sub["ln_in"]}, "mixer": _mixer_to_program(sub["attn"], cfg),
                 "ln2": {"scale": sub["ln_post"]},
                 "mlp": {"w_in": sub["ff_in"], "w_out": sub["ff_out"]}}
            if at == 0:
                p["moe"] = {"router": lp["router"], "bias": lp["router_bias"],
                            "w_in": lp["experts_in"], "w_out": lp["experts_out"]}
            blocks.append(p)
    return {"embed": {"table": top["wte"]}, "blocks": blocks,
            "ln": {"scale": top["lnf"]}, "head": {"table": top["head"].T}}


def to_reference(tree: dict) -> dict:
    """The program's tree under the reference's names."""
    def mixer(m):
        kv = jnp.concatenate([m["w_uk"].transpose(2, 0, 1), m["w_uv"].transpose(1, 0, 2)], axis=-1)
        return {"w_dq": m["w_dq"], "q_norm": m["q_norm"]["scale"], "w_uq": m["w_uq"].T,
                "w_dkv": m["w_dkv"], "kv_norm": m["kv_norm"]["scale"],
                "w_ukv": kv.reshape(kv.shape[0], -1), "w_o": m["w_out"]}

    def sub(b):
        return {"ln_in": b["ln1"]["scale"], "attn": mixer(b["mixer"]), "ln_post": b["ln2"]["scale"],
                "ff_in": b["mlp"]["w_in"], "ff_out": b["mlp"]["w_out"]}

    blocks = tree["blocks"]
    layers = [{"sub": [sub(b) for b in blocks[at:at + SPAN]],
               "router": blocks[at]["moe"]["router"], "router_bias": blocks[at]["moe"]["bias"],
               "experts_in": blocks[at]["moe"]["w_in"], "experts_out": blocks[at]["moe"]["w_out"]}
              for at in range(0, len(blocks), SPAN)]
    return {"wte": tree["embed"]["table"], "lnf": tree["ln"]["scale"],
            "head": tree["head"]["table"].T, "layers": layers}


def make_init(cfg: dict, dtype, *, layout: str):
    """``key -> weights`` in ``layout`` 'program' or 'reference'.  Both run
    ONE jitted `reference.init_parts` (the calibrated bias is then the same
    numbers whichever layout asks) and lay its parts out after it, leaf by
    leaf: a second program over the whole tree would hold the weights twice."""
    parts = jax.jit(lambda key: reference.init_parts(key, cfg, jnp.dtype(dtype)))

    def init(key):
        top, layers = parts(key)
        return to_program(top, layers, cfg) if layout == "program" else {**top, "layers": layers}

    return init


def mixer_sizes(cfg: dict) -> dict:
    """What `HybridLM` builds its one kind of mixer with."""
    return {"latent_attention": dict(
        heads=cfg["num_attention_heads"], q_rank=cfg["q_lora_rank"], kv_rank=cfg["kv_lora_rank"],
        nope_dim=cfg["qk_nope_head_dim"], rope_dim=cfg["qk_rope_head_dim"],
        v_dim=cfg["v_head_dim"], rope_base=float(cfg["rope_theta"]), gated=False)}


def make_lm(cfg: dict, seeded_key, dtype, *, remat: bool = False):
    """The program's `HybridLM` at the configuration's sizes, whose ``init``
    is the benchmark's seeded generator at ``seeded_key``."""
    from tpu_dist.models.hybrid_lm import HybridLM

    del remat   # the family serves only
    reference.sizes(cfg)   # the keys the program computes one form of
    init = make_init(cfg, dtype, layout="program")

    class Seeded(HybridLM):
        def init(self, key=None, input_shape=None):
            del key, input_shape
            return init(seeded_key), {}

    return Seeded(
        vocab=cfg["vocab_size"], dim=cfg["hidden_size"],
        layer_types=["latent_attention"] * _sublayers(cfg), mixers=mixer_sizes(cfg),
        shortcut=SPAN, n_experts=cfg["router_experts"] + cfg["zero_expert_num"],
        zero_experts=cfg["zero_expert_num"], experts_per_token=cfg["moe_topk"],
        expert_width=cfg["expert_ffn_hidden_size"], held_experts=tuple(cfg["held_experts"]),
        expert_scoring="softmax", route_scale=float(cfg["routed_scaling_factor"]),
        dense_width=cfg["ffn_hidden_size"], tied_head=False, norm_eps=cfg["rms_norm_eps"],
        max_seq=cfg["max_position_embeddings"],
    )
