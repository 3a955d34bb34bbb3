"""qwen2-7b [dense]: 28L, d_model=3584, 28H (GQA kv=4), d_ff=18944,
vocab=152064. QKV bias, RoPE theta=1e6, SwiGLU, RMSNorm. [arXiv:2407.10671]

``qwen2-7b-1chip`` is the same model cut to fit one TPU v5e (16 GB):

- source: Qwen2 technical report, arXiv:2407.10671 (the 7B column);
- kept as published: every width (d_model 3584, 28 query / 4 KV heads of
  128, d_ff 18944, vocab 152064), QKV bias, rope theta 1e6, bf16;
- reduced: n_layers 28 -> 16. The layer pattern has period 1, so any
  depth keeps every kind of layer. Weights are 16 x 466 MB of layers plus
  2 x 1.09 GB of embedding and head, about 9.6 GB, leaving about 6 GB for
  the page pool and activations;
- deployment it stands for: a two-stage pipeline over two chips, this
  chip holding the first 16 layers (with the embedding and the head) and
  a second chip the other 12. No layer is split across chips.
"""
from dataclasses import replace

from repro.configs.base import ArchConfig, register


@register("qwen2-7b")
def config() -> ArchConfig:
    return ArchConfig(
        name="qwen2-7b",
        family="dense",
        n_layers=28,
        d_model=3584,
        n_heads=28,
        n_kv_heads=4,
        head_dim=128,
        d_ff=18944,
        vocab=152064,
        mlp="swiglu",
        qkv_bias=True,
        rope_theta=1e6,
    )


@register("qwen2-7b-1chip")
def one_chip() -> ArchConfig:
    return replace(config(), name="qwen2-7b-1chip", n_layers=16)
