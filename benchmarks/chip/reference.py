"""Plain float32 Qwen2 forward, the yardstick of ``correct``.

Qwen2 (arXiv:2407.10671): pre-norm decoder; RMSNorm; grouped-query
attention with q, k and v biases and rotary embeddings (theta from the
config, the two halves of each head rotated as a pair); SwiGLU MLP; an
untied vocabulary projection. Nothing here imports the program. Weights
are drawn again layer by layer from the seed (``weights.py``), upcast to
float32, and every matmul runs at ``highest`` precision. Rows go through
in groups of ``rows``, one layer at a time, so a 7B forward fits beside
nothing else on one chip.

``quant="fp8"`` is the control: the same forward with both operands of
every projection (weights per output column, activations per token)
scaled to the range of float8_e4m3fn and rounded to it, the step below
the bfloat16 that the configuration serves in. It has to fail the
comparison. (int8, symmetric per column and per token, was tried first
and did not separate from sound bf16 runs at the test size.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import weights as W
from benchmarks.chip.work import Dims

Q_BLOCK = 512


def _f8(x, axis):
    """x rounded to float8_e4m3fn after scaling its absmax to 448."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant is None:
        return jnp.einsum("...d,df->...f", x, w)
    if quant != "fp8":
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum("...d,df->...f", _f8(x, -1), _f8(w, 0))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (B, S, H, dh), positions 0..S-1."""
    dh, S = x.shape[-1], x.shape[1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv       # (S, dh/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal GQA in query blocks. q: (B, S, H, dh); k, v: (B, S, Hkv, dh)."""
    B, S, H, dh = q.shape
    hkv = k.shape[2]
    q = q.reshape(B, S, hkv, H // hkv, dh) * dh ** -0.5
    outs = []
    for s0 in range(0, S, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        n = qb.shape[1]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, k)
        ok = (jnp.arange(S)[None, :]
              <= (s0 + jnp.arange(n))[:, None])                 # (n, S)
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("bhgqk,bkhd->bqhgd", p, v))
    return jnp.concatenate(outs, 1).reshape(B, S, H * dh)


@functools.partial(jax.jit, static_argnames=("m", "eps", "theta", "quant"))
def _layer(x, key, l, *, m: Dims, eps, theta, quant):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), W.layer(key, l, m))
    B, S, _ = x.shape
    h = _rms(x, w["ln1"], eps)
    q = (_mm(h, w["wq"], quant) + w["bq"]).reshape(B, S, m.heads, m.head_dim)
    k = (_mm(h, w["wk"], quant) + w["bk"]).reshape(B, S, m.kv_heads,
                                                    m.head_dim)
    v = (_mm(h, w["wv"], quant) + w["bv"]).reshape(B, S, m.kv_heads,
                                                    m.head_dim)
    a = _attention(_rope(q, theta), _rope(k, theta), v)
    x = x + _mm(a, w["wo"], quant)
    h = _rms(x, w["ln2"], eps)
    gate, up = jnp.split(w["w_up"], 2, axis=-1)
    g = jax.nn.silu(_mm(h, gate, quant)) * _mm(h, up, quant)
    return x + _mm(g, w["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("m",))
def _embed(tokens, key, *, m: Dims):
    return W.embed(key, m)[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("m", "eps", "quant"))
def _head(x, key, *, m: Dims, eps, quant):
    h = _rms(x, W.final_norm(key, m).astype(jnp.float32), eps)
    return _mm(h, W.lm_head(key, m).astype(jnp.float32), quant)


def logits(seed: int, model: dict, tokens: np.ndarray, rows_at: np.ndarray,
           quant=None) -> np.ndarray:
    """float32 logits of ``tokens`` ((B, S) ids) at positions ``rows_at``
    ((B, T), each < S): (B, T, vocab). One call is one group of rows; the
    caller picks the group size."""
    m = Dims.of(model)
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    key = W.seed_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(jnp.asarray(tokens), key, m=m)
        for l in range(m.layers):
            x = _layer(x, key, jnp.int32(l), m=m, eps=eps, theta=theta,
                       quant=quant)
        x = jnp.take_along_axis(x, jnp.asarray(rows_at)[..., None], axis=1)
        return _head(x, key, m=m, eps=eps, quant=quant)


@jax.jit
def _gaps(ref, served, other):
    """How far below the reference's best logit the served token lies, and
    the token that ``other`` logits put first."""
    best = jnp.max(ref, -1)
    g_served = best - jnp.take_along_axis(ref, served[..., None], -1)[..., 0]
    pick = jnp.argmax(other, -1)
    g_other = best - jnp.take_along_axis(ref, pick[..., None], -1)[..., 0]
    return g_served, g_other


def served_gaps(seed: int, model: dict, requests, *, rows: int,
                seq_len: int, out_len: int, control=None):
    """Widest gap, over every served token of ``requests``, by which the
    served token's reference logit lies below the reference's best; with
    ``control`` (a ``quant`` name) also the widest gap of the token that
    control puts first at the same positions. Each request is (prefilled ids, served
    ids): the ids as the runtime prefilled them (pad included) and the
    tokens it delivered, the first from the prefill. Every group is padded
    to ``rows`` x ``seq_len`` ids and ``out_len`` served tokens, so that
    one set of programs serves every run."""
    worst, worst_ctrl = 0.0, 0.0
    for g0 in range(0, len(requests), rows):
        grp = requests[g0:g0 + rows]
        tlen = out_len
        toks = np.zeros((rows, seq_len), np.int32)
        at = np.zeros((rows, tlen), np.int32)
        served = np.zeros((rows, tlen), np.int32)
        mask = np.zeros((rows, tlen), bool)
        for i, (p, s) in enumerate(grp):
            seq = np.concatenate([p, s[:-1]])
            if seq.shape[0] > seq_len:
                raise ValueError(f"sequence of {seq.shape[0]} > {seq_len}")
            toks[i, :seq.shape[0]] = seq
            n = len(s)
            at[i, :n] = len(p) - 1 + np.arange(n)
            served[i, :n] = s
            mask[i, :n] = True
        ref = logits(seed, model, toks, at)
        other = logits(seed, model, toks, at, quant=control) if control \
            else ref
        g_s, g_o = (np.asarray(a) for a in _gaps(ref, jnp.asarray(served),
                                                 other))
        worst = max(worst, float(g_s[mask].max()))
        worst_ctrl = max(worst_ctrl, float(g_o[mask].max()))
        del ref, other
    return (worst, worst_ctrl) if control else (worst, None)
