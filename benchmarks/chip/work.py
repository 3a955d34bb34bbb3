"""Operations and bytes the served work needs, computed from shapes.

Every count covers only what the traffic asked for: the requested prompt
tokens (not the pad up to the length bucket), the context each delivered
token attends over (not the kv bucket or the last page the kernel walks),
and the rows that delivered a token (not pad rows or frozen rows). Padded
or dead work therefore lowers a share and can never lift it. ``tp`` divides
the per-chip share of a tensor-parallel replica (heads, MLP columns and
vocabulary split evenly over ``tp`` chips).
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


@dataclass(frozen=True)
class Dims:
    d: int          # hidden size
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int        # intermediate size (one of the two gated halves)
    vocab: int

    @classmethod
    def of(cls, model: dict) -> "Dims":
        """From a Hugging Face style config (``hidden_size`` ...)."""
        d, h = model["hidden_size"], model["num_attention_heads"]
        return cls(d=d, layers=model["num_hidden_layers"], heads=h,
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model.get("head_dim", d // h),
                   ffn=model["intermediate_size"],
                   vocab=model["vocab_size"])

    @property
    def layer_matmul_params(self) -> int:
        """Weights one token multiplies through in one layer (q, k, v, o
        projections and the gated MLP's three matrices)."""
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        return self.d * q + 2 * self.d * kv + q * self.d + 3 * self.d * self.ffn


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by JAX's ``device_kind``. A device
    missing from ``peaks.json`` is an error, never a default."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def attn_decode(m: Dims, ctx: int, tp: int = 1, kv_bytes: int = 2):
    """One decode token's paged attention over ``ctx`` live entries, all
    layers, on one of ``tp`` chips: (flops, bytes). FLOPs: q.k and p.v,
    2 * ctx * head_dim each per query head. Bytes: the K and V entries of
    the context, the query read and the output written."""
    heads, kvh = m.heads // tp, m.kv_heads // tp
    flops = 4 * ctx * heads * m.head_dim * m.layers
    byts = (2 * ctx * kvh * m.head_dim + 2 * heads * m.head_dim) \
        * kv_bytes * m.layers
    return flops, byts


def decode_token_flops(m: Dims, ctx: int, tp: int = 1) -> int:
    """The whole model step for one delivered decode token at context
    ``ctx`` (the new token included), per chip: every layer's matmuls,
    its attention, and the vocabulary projection."""
    return (2 * m.layer_matmul_params * m.layers + 2 * m.d * m.vocab) // tp \
        + attn_decode(m, ctx, tp)[0]


def prefill_flops(m: Dims, plen: int, tp: int = 1) -> int:
    """Prefill of ``plen`` requested prompt tokens, per chip: matmuls of
    every token, causal attention (token i attends over i + 1 entries)
    and one row of the vocabulary projection for the first output
    token."""
    attn = 4 * m.heads * m.head_dim * m.layers * plen * (plen + 1) // 2
    return (2 * m.layer_matmul_params * m.layers * plen + attn
            + 2 * m.d * m.vocab) // tp


def roofline_s(flops: float, byts: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])
