"""ViT-Tiny — extended config 5 (BASELINE.json: "ViT-Tiny / ImageNet-1k,
stress allreduce bandwidth at pod scale").

Standard ViT-Ti/16: dim 192, depth 12, heads 3, MLP ratio 4, learned
position embeddings, CLS token.  Built from `tpu_dist.nn` primitives; the
attention core is `tpu_dist.nn.dot_product_attention`, the same function
the sequence-parallel ring path shards (`tpu_dist.parallel.ring_attention`),
so single-device and ring-sharded execution are numerically comparable.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from tpu_dist import nn
from tpu_dist.nn.core import Module


class MLP(Module):
    def __init__(self, dim: int, hidden: int):
        self.fc1 = nn.Dense(hidden)
        self.fc2 = nn.Dense(dim)

    def init(self, key, input_shape):
        k1, k2 = jax.random.split(key)
        p1, _ = self.fc1.init(k1, input_shape)
        p2, _ = self.fc2.init(k2, self.fc1.out_shape(input_shape))
        return {"fc1": p1, "fc2": p2}, {}

    def apply(self, params, state, x, *, train=False, key=None):
        h, _ = self.fc1.apply(params["fc1"], {}, x)
        h = jax.nn.gelu(h)
        h, _ = self.fc2.apply(params["fc2"], {}, h)
        return h, state


class EncoderBlock(Module):
    """Pre-norm transformer block: x + MHA(LN(x)); x + MLP(LN(x))."""

    def __init__(self, dim: int, heads: int, mlp_ratio: int = 4, *,
                 causal: bool = False, kv_heads: int | None = None,
                 use_rope: bool = False, sliding_window: int | None = None):
        self.ln1 = nn.LayerNorm()
        self.attn = nn.MultiHeadAttention(
            dim, heads, causal=causal, kv_heads=kv_heads, use_rope=use_rope,
            sliding_window=sliding_window,
        )
        self.ln2 = nn.LayerNorm()
        self.mlp = MLP(dim, dim * mlp_ratio)

    def init(self, key, input_shape):
        ks = jax.random.split(key, 4)
        pl1, _ = self.ln1.init(ks[0], input_shape)
        pa, _ = self.attn.init(ks[1], input_shape)
        pl2, _ = self.ln2.init(ks[2], input_shape)
        pm, _ = self.mlp.init(ks[3], input_shape)
        return {"ln1": pl1, "attn": pa, "ln2": pl2, "mlp": pm}, {}

    def apply(self, params, state, x, *, train=False, key=None, mask=None):
        with jax.named_scope("block/attn"):
            h, _ = self.ln1.apply(params["ln1"], {}, x)
            h, _ = self.attn.apply(params["attn"], {}, h, mask=mask)
            x = x + h
        with jax.named_scope("block/mlp"):
            h, _ = self.ln2.apply(params["ln2"], {}, x)
            h, _ = self.mlp.apply(params["mlp"], {}, h)
            return x + h, state


class ViT(Module):
    def __init__(
        self,
        *,
        image_size: int = 224,
        patch: int = 16,
        dim: int = 192,
        depth: int = 12,
        heads: int = 3,
        num_classes: int = 1000,
        channels: int = 3,
    ):
        if image_size % patch:
            raise ValueError(f"image size {image_size} not divisible by patch {patch}")
        self.patch = patch
        self.dim = dim
        self.num_tokens = (image_size // patch) ** 2 + 1  # + CLS
        self.embed = nn.Conv2D(dim, patch, stride=patch)
        self.blocks = [EncoderBlock(dim, heads) for _ in range(depth)]
        self.ln = nn.LayerNorm()
        self.head = nn.Dense(num_classes)
        self.in_shape = (image_size, image_size, channels)

    def init(self, key, input_shape):
        ks = jax.random.split(key, len(self.blocks) + 4)
        pe, _ = self.embed.init(ks[0], input_shape)
        tok_shape = (self.num_tokens, self.dim)
        params = {
            "embed": pe,
            "cls": jnp.zeros((1, 1, self.dim)),
            "pos": jax.random.normal(ks[1], (1, self.num_tokens, self.dim)) * 0.02,
            "blocks": [],
            "ln": self.ln.init(ks[2], tok_shape)[0],
            "head": self.head.init(ks[3], tok_shape)[0],
        }
        for blk, k in zip(self.blocks, ks[4:]):
            pb, _ = blk.init(k, tok_shape)
            params["blocks"].append(pb)
        return params, {}

    def out_shape(self, input_shape):
        return (self.head.features,)

    def apply(self, params, state, x, *, train=False, key=None):
        b = x.shape[0]
        h, _ = self.embed.apply(params["embed"], {}, x)  # (b, H/p, W/p, dim)
        h = h.reshape(b, -1, self.dim)
        cls = jnp.broadcast_to(params["cls"], (b, 1, self.dim))
        h = jnp.concatenate([cls, h], axis=1) + params["pos"]
        for blk, pb in zip(self.blocks, params["blocks"]):
            h, _ = blk.apply(pb, {}, h, train=train)
        h, _ = self.ln.apply(params["ln"], {}, h)
        logits, _ = self.head.apply(params["head"], {}, h[:, 0])
        return logits, state


def vit_tiny(
    image_size: int = 224, patch: int = 16, num_classes: int = 1000
) -> ViT:
    return ViT(
        image_size=image_size, patch=patch, num_classes=num_classes
    )
