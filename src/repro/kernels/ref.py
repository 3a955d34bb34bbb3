"""Pure-jnp oracles for every Pallas kernel (naive, O(S^2)/sequential)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def attention_ref(q, k, v, *, causal=True, window=None, chunk=None,
                  kv_len=None, softcap=0.0):
    """q: (B,Hq,Sq,dh); k,v: (B,Hkv,Sk,dh). Naive materialized softmax."""
    B, Hq, Sq, dh = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    kg = jnp.repeat(k, G, axis=1)
    vg = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * (dh ** -0.5)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(Sk)[None, :]
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= (qpos - kpos) < window
    if chunk is not None:
        ok &= (qpos // chunk) == (kpos // chunk)
    if kv_len is not None:
        ok &= kpos < kv_len
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vg.astype(jnp.float32)).astype(q.dtype)


def decode_attention_ref(q, k, v, *, lengths, window=None, chunk=None,
                         softcap=0.0):
    """q: (B,Hq,dh); k,v: (B,Skmax,Hkv,dh); lengths: (B,) valid cache length.
    Query position = lengths - 1."""
    B, Hq, dh = q.shape
    Sk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    kg = jnp.repeat(k, G, axis=2)
    vg = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bhd,bkhd->bhk", q.astype(jnp.float32),
                   kg.astype(jnp.float32)) * (dh ** -0.5)
    if softcap:
        s = jnp.tanh(s / softcap) * softcap
    qpos = (lengths - 1)[:, None, None]
    kpos = jnp.arange(Sk)[None, None, :]
    ok = kpos < lengths[:, None, None]
    if window is not None:
        ok &= (qpos - kpos) < window
    if chunk is not None:
        ok &= (qpos // chunk) == (kpos // chunk)
    s = jnp.where(ok, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkhd->bhd", p, vg.astype(jnp.float32)).astype(q.dtype)


def mlstm_ref(q, k, v, li, lf, state=None):
    """Sequential stabilized mLSTM recurrence. q,k,v: (B,S,H,dh) (k pre-scaled);
    li, lf: (B,S,H) raw gates. Returns (h, (C, n, m))."""
    B, S, H, dh = q.shape
    f32 = jnp.float32
    if state is None:
        C = jnp.zeros((B, H, dh, dh), f32)
        n = jnp.zeros((B, H, dh), f32)
        m = jnp.full((B, H), NEG_INF, f32)
    else:
        C, n, m = (s.astype(f32) for s in state)
    lf = jax.nn.log_sigmoid(lf.astype(f32))
    li = li.astype(f32)

    def step(carry, xs):
        C, n, m = carry
        qt, kt, vt, it, ft = xs
        m_new = jnp.maximum(ft + m, it)
        fp = jnp.exp(ft + m - m_new)
        ip = jnp.exp(it - m_new)
        C = fp[..., None, None] * C + ip[..., None, None] * (
            kt[..., :, None] * vt[..., None, :])
        n = fp[..., None] * n + ip[..., None] * kt
        den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", qt, n)),
                          jnp.exp(-m_new))
        h = jnp.einsum("bhd,bhde->bhe", qt, C) / den[..., None]
        return (C, n, m_new), h

    xs = (q.astype(f32).swapaxes(0, 1), k.astype(f32).swapaxes(0, 1),
          v.astype(f32).swapaxes(0, 1), li.swapaxes(0, 1), lf.swapaxes(0, 1))
    (C, n, m), hs = jax.lax.scan(step, (C, n, m), xs)
    return hs.swapaxes(0, 1).astype(q.dtype), (C, n, m)


def ssm_ref(u, dt, A, Bsel, Csel, Dskip, h0=None):
    """Sequential selective-SSM recurrence. u, dt: (B,S,di); A: (di,N);
    Bsel, Csel: (B,S,N). Returns (y (B,S,di), h_last (B,di,N))."""
    B, S, di = u.shape
    N = A.shape[1]
    h = jnp.zeros((B, di, N), jnp.float32) if h0 is None else h0.astype(jnp.float32)

    def step(h, xs):
        ut, dtt, Bt, Ct = xs
        Ad = jnp.exp(dtt[..., None] * A)
        h = Ad * h + (dtt * ut)[..., None] * Bt[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, Ct) + Dskip * ut
        return h, y

    xs = (u.astype(jnp.float32).swapaxes(0, 1), dt.astype(jnp.float32).swapaxes(0, 1),
          Bsel.astype(jnp.float32).swapaxes(0, 1), Csel.astype(jnp.float32).swapaxes(0, 1))
    h, ys = jax.lax.scan(step, h, xs)
    return ys.swapaxes(0, 1).astype(u.dtype), h
