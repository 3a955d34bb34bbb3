"""The Qwen2 family (``"model_type": "qwen2"``): a thin adapter over
``weights.py`` (seeded weights), ``reference.py`` (the float32 forward and
its controls, ``fp8`` and ``tp_local``) and ``work.py`` (the work counts),
which are this family's code. ``run_cell.load_family`` lists what a
family provides.
"""
from __future__ import annotations

from benchmarks.chip import weights as W
from benchmarks.chip.reference import served_gaps  # noqa: F401
from benchmarks.chip.weights import seed_key  # noqa: F401
from benchmarks.chip.work import (Dims, attn_decode,  # noqa: F401
                                  decode_token_flops, prefill_flops)


def dims(model: dict) -> Dims:
    return Dims.of(model)


def check(cfg, model: dict) -> None:
    """The program's configuration has to be the one the file states."""
    m = Dims.of(model)
    want = {"d_model": m.d, "n_layers": m.layers, "n_heads": m.heads,
            "n_kv_heads": m.kv_heads, "head_dim": m.head_dim, "d_ff": m.ffn,
            "vocab": m.vocab, "rope_theta": float(model["rope_theta"]),
            "norm_eps": float(model["rms_norm_eps"]),
            "qkv_bias": True, "mlp": "swiglu", "tie_embeddings": False}
    have = {k: getattr(cfg, k) for k in want}
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"cell's file: {bad}")


def program_params(key, model: dict, dtype) -> dict:
    return W.program_params(key, Dims.of(model), dtype)
