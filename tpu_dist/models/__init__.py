"""`tpu_dist.models` — model zoo.

The parity MNIST ConvNet (train_dist.py:53-71 architecture) plus the
extended-config families: ResNet-18 (CIFAR-10) and ViT-Tiny (ImageNet),
BASELINE.json configs 4-5; `HybridLM`, a decoder of several layer kinds
(state-space and attention mixers over routed experts).
"""

from tpu_dist.models.hybrid_lm import HybridLM
from tpu_dist.models.mnist_net import IN_SHAPE, NUM_CLASSES, mnist_net
from tpu_dist.models.resnet import BasicBlock, resnet18
from tpu_dist.models.transformer_lm import (
    TransformerLM,
    lm_loss,
    lm_loss_seq_parallel,
    lm_perplexity,
    markov_table,
    synthetic_tokens,
)
from tpu_dist.models.vit import ViT, vit_tiny

__all__ = [
    "BasicBlock",
    "HybridLM",
    "IN_SHAPE",
    "NUM_CLASSES",
    "TransformerLM",
    "ViT",
    "lm_loss",
    "lm_loss_seq_parallel",
    "lm_perplexity",
    "markov_table",
    "mnist_net",
    "resnet18",
    "synthetic_tokens",
    "vit_tiny",
]
