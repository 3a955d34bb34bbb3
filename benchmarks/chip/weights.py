"""Seeded weights of a Qwen2-style decoder, made by the benchmark.

Both sides read them from here: the harness hands them to the program,
and the reference draws each layer again on its own. Every leaf comes from
``fold_in`` of the seed's key with the layer and the leaf's index, so a
layer can be drawn alone and gives the same numbers as the stacked
draw. Values are rounded to the served dtype; the reference computes on
the same rounded values in float32.

Scales: projections 1 / sqrt(fan_in); embedding 1; norm weights
1 + N(0, 0.1); q, k and v biases N(0, 0.1), so the bias path is checked.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.work import Dims

LAYER_LEAVES = ("ln1", "wq", "bq", "wk", "bk", "wv", "bv", "wo", "ln2",
                "w_up", "w_down")


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number (SeedSequence takes ints of
    any size)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def _leaf(key, i, shape, std, centre=0.0, dtype=jnp.bfloat16):
    x = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
    return (x * std + centre).astype(dtype)


def layer(key, l, m: Dims, dtype=jnp.bfloat16) -> dict:
    """Layer ``l`` (traced or static), in the ``x @ W`` convention. The
    MLP's gate and up projections are one (d, 2 * ffn) leaf, gate first."""
    k = jax.random.fold_in(jax.random.fold_in(key, 1), l)
    q, kv, d, f = m.heads * m.head_dim, m.kv_heads * m.head_dim, m.d, m.ffn
    shapes = {"ln1": ((d,), 0.1, 1.0), "wq": ((d, q), d ** -0.5, 0.0),
              "bq": ((q,), 0.1, 0.0), "wk": ((d, kv), d ** -0.5, 0.0),
              "bk": ((kv,), 0.1, 0.0), "wv": ((d, kv), d ** -0.5, 0.0),
              "bv": ((kv,), 0.1, 0.0), "wo": ((q, d), q ** -0.5, 0.0),
              "ln2": ((d,), 0.1, 1.0), "w_up": ((d, 2 * f), d ** -0.5, 0.0),
              "w_down": ((f, d), f ** -0.5, 0.0)}
    return {nm: _leaf(k, i, *shapes[nm], dtype=dtype)
            for i, nm in enumerate(LAYER_LEAVES)}


def embed(key, m: Dims, dtype=jnp.bfloat16):
    return _leaf(jax.random.fold_in(key, 2), 0, (m.vocab, m.d), 1.0,
                 dtype=dtype)


def final_norm(key, m: Dims, dtype=jnp.bfloat16):
    return _leaf(jax.random.fold_in(key, 3), 0, (m.d,), 0.1, 1.0,
                 dtype=dtype)


def lm_head(key, m: Dims, dtype=jnp.bfloat16):
    return _leaf(jax.random.fold_in(key, 4), 0, (m.d, m.vocab),
                 m.d ** -0.5, dtype=dtype)


def program_params(key, m: Dims, dtype=jnp.bfloat16) -> dict:
    """All weights in the serving program's parameter tree
    (``models/transformer.py``: layers stacked under ``dense_layers``)."""
    stacked = jax.vmap(lambda l: layer(key, l, m, dtype))(
        jnp.arange(m.layers))
    return {"embed": embed(key, m, dtype),
            "final_norm": final_norm(key, m, dtype),
            "lm_head": lm_head(key, m, dtype),
            "dense_layers": stacked}
