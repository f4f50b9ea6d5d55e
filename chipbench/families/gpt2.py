"""Family ``gpt2``: everything the harness has to know of one architecture.

A configuration file names its family (``"family": "gpt2"``); the harness
finds ``<path>/families/<name>.py`` by that name, as it finds a per-layer
reader, and calls nothing of an architecture but what a family file
offers.  A later PR that brings another architecture (rope, grouped
queries, experts) adds a file like this one, its plain reference beside it
and its configuration files, and edits nothing that is there.

What a family file offers (``cfg`` is the configuration file's object):

- ``vocab_size(cfg)``; ``param_count(cfg)``;
  ``forward_flops_per_token(cfg, seq_len)`` and
  ``kv_bytes_per_token(cfg, bytes_per_value)``: the operations and bytes
  the algorithm requires, for ``mfu`` and roofline shares; beside them the
  count a kernel's own roofline metric needs (here
  ``attention_flops_per_token``, for ``flash_roofline_pct.train``);
- ``tiny(cfg)``: the overrides, by this family's own key names, that take
  a configuration to the size the CPU rehearsal runs (under 5 M
  parameters); a ``serve`` / ``train`` / ``limits`` entry in it overrides
  the rehearsal's common sizes of that section.  A family without it
  fails the rehearsal at once: nothing is rehearsed at published widths;
- optional: ``SCOPES`` (the `jax.named_scope` names of its layers, which
  join `chipbench.scopes.SCOPES`), ``KERNELS`` (its Pallas kernels'
  `pl.pallas_call(name=)`), ``state_bytes_per_slot(cfg)`` (a recurrent
  state a decode step reads and writes for every busy slot).  This
  family's scopes and kernels are the program's base vocabulary already
  and it keeps no such state, so it offers none of the three;
- ``make_lm(cfg, seeded_key, dtype, remat=)``: the program's model at the
  configuration's sizes, whose ``init`` returns the seeded weights;
- ``make_init(cfg, dtype, layout=)``: a jitted ``key -> weights`` in the
  program's tree (``"program"``) or the reference's (``"reference"``),
  the same values in both; ``to_reference(tree)`` maps the program's tree
  (its gradients, its moments) onto the reference's names;
- ``reference``: the plain reference, with ``forward(p, tokens, cfg,
  quant=)``, ``loss_and_grad(p, rows, cfg, quant=)``, ``adam_init(p)``,
  ``adam_update(p, g, st, lr=)`` and ``leaf_norms(tree)``.

The benchmark makes the weights, on the device in one jitted call from the
seed; program and reference are both given them, so the reference takes
nothing the program has made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.reference import gpt2_ref as reference


def vocab_size(cfg: dict) -> int:
    return int(cfg["vocab_size"])


def param_count(cfg: dict) -> int:
    """Parameters of a GPT-2 configuration (tied head counted once)."""
    L, D, V, S = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"], cfg["n_positions"]
    per_block = 12 * D * D + 13 * D  # qkv, proj, fc, fc2 with biases, two LayerNorms
    return V * D + S * D + L * per_block + 2 * D


def tiny(cfg: dict) -> dict:
    """The rehearsal's size of any configuration of this family."""
    del cfg
    return {"n_embd": 64, "n_layer": 2, "n_head": 4, "n_positions": 128, "n_ctx": 128,
            "vocab_size": 512,
            # wide enough that two blocks of width 64 outweigh the embedding:
            # at the published 0.02 the tied head just echoes the last token
            "initializer_range": 0.15}


def attention_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Operations the attention of all layers requires for one token's
    forward pass at ``seq_len``: QK^T and PV are 4*D operations per visible
    key, and a causal query sees (seq_len + 1) / 2 keys on average."""
    return cfg["n_layer"] * 4 * cfg["n_embd"] * (seq_len + 1) / 2


def forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Matrix-product operations one token's forward pass requires at
    ``seq_len``: the four block products, causal attention over the
    realisable scores, and the tied head."""
    L, D, V = cfg["n_layer"], cfg["n_embd"], cfg["vocab_size"]
    return L * 2 * 12 * D * D + attention_flops_per_token(cfg, seq_len) + 2 * V * D


def kv_bytes_per_token(cfg: dict, bytes_per_value: int) -> int:
    return 2 * cfg["n_layer"] * cfg["n_embd"] * bytes_per_value


def to_program(top: dict, blocks: list[dict]) -> dict:
    """`reference.init_parts` -> the tree `TransformerLM.init` returns."""
    return {
        "embed": {"table": top["wte"]},
        "blocks": [{
            "ln1": {"scale": b["ln1_g"], "bias": b["ln1_b"]},
            "attn": {
                "qkv": {"w": b["attn_w"], "b": b["attn_b"]},
                "out": {"w": b["proj_w"], "b": b["proj_b"]},
            },
            "ln2": {"scale": b["ln2_g"], "bias": b["ln2_b"]},
            "mlp": {
                "fc1": {"w": b["fc_w"], "b": b["fc_b"]},
                "fc2": {"w": b["fc2_w"], "b": b["fc2_b"]},
            },
        } for b in blocks],
        "ln": {"scale": top["lnf_g"], "bias": top["lnf_b"]},
        "pos": top["wpe"][None],
    }


def to_reference(tree: dict) -> dict:
    """The program's tree in the reference's stacked layout, for comparing
    leaf by leaf."""
    b = tree["blocks"]
    stack = lambda f: jnp.stack([f(x) for x in b])  # noqa: E731
    return {
        "wte": tree["embed"]["table"],
        "wpe": tree["pos"][0],
        "ln1_g": stack(lambda x: x["ln1"]["scale"]),
        "ln1_b": stack(lambda x: x["ln1"]["bias"]),
        "attn_w": stack(lambda x: x["attn"]["qkv"]["w"]),
        "attn_b": stack(lambda x: x["attn"]["qkv"]["b"]),
        "proj_w": stack(lambda x: x["attn"]["out"]["w"]),
        "proj_b": stack(lambda x: x["attn"]["out"]["b"]),
        "ln2_g": stack(lambda x: x["ln2"]["scale"]),
        "ln2_b": stack(lambda x: x["ln2"]["bias"]),
        "fc_w": stack(lambda x: x["mlp"]["fc1"]["w"]),
        "fc_b": stack(lambda x: x["mlp"]["fc1"]["b"]),
        "fc2_w": stack(lambda x: x["mlp"]["fc2"]["w"]),
        "fc2_b": stack(lambda x: x["mlp"]["fc2"]["b"]),
        "lnf_g": tree["ln"]["scale"],
        "lnf_b": tree["ln"]["bias"],
    }


def make_init(cfg: dict, dtype, *, layout: str):
    """A jitted ``key -> weights`` in ``layout`` 'program' or 'reference'."""
    def fn(key):
        if layout == "program":  # block by block: nothing is stacked on the way
            return to_program(*reference.init_parts(key, cfg, jnp.dtype(dtype)))
        return reference.init(key, cfg, jnp.dtype(dtype))

    return jax.jit(fn)


def make_lm(cfg: dict, seeded_key, dtype, *, remat: bool = False):
    """The program's `TransformerLM` at the configuration's sizes, whose
    ``init`` is the benchmark's seeded generator at ``seeded_key``
    (whatever key the caller passes): a trainer that initialises its own
    model gets these weights."""
    from tpu_dist.models.transformer_lm import TransformerLM

    init = make_init(cfg, dtype, layout="program")

    class SeededGPT2(TransformerLM):
        def init(self, key=None, input_shape=None):
            del key, input_shape
            return init(seeded_key), {}

    return SeededGPT2(
        vocab=cfg["vocab_size"], dim=cfg["n_embd"], depth=cfg["n_layer"],
        heads=cfg["n_head"], max_seq=cfg["n_positions"], pos_embedding="learned",
        remat=remat,
    )
