"""Pallas TPU paged decode attention (vLLM-style PagedAttention).

The KV cache is a shared physical pool of fixed-size pages, stored
head-major: ``(n_pages, Hkv, page_size, dh)`` per layer. Each batch row
owns a small page table mapping its logical KV blocks to physical pages.

The grid is ``(B, logical_pages)``. Step ``(b, j)`` loads one whole
physical page of row ``b`` — every KV head at once, block
``(1, Hkv, page_size, dh)`` — and the row's query as a
``(1, Hkv, G, dh)`` block (the ``G = Hq // Hkv`` query heads of each KV
group side by side), so each page is fetched once for all query heads
that read it. Both blocks end in dimensions the TPU tiling accepts:
``(page_size, dh)`` with page_size a multiple of 8, and ``(G, dh)``
equal to the array's own trailing dimensions.

The page table rides in as a scalar-prefetch operand, so the BlockSpec
index map fetches each row's *physical* page. Pages at or beyond a row's
live length repeat the last live page's block index (no new DMA) and
their compute is skipped by ``pl.when`` — decode work tracks the tokens a
request holds, not the pool. The streaming log-sum-exp reduction matches
the jnp path's masking semantics (causal-by-length, sliding window,
chunked, logit soft-cap).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _kernel(pages_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale, window, chunk, softcap, ps,
            n_pg, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    qpos = length - 1

    @pl.when(j * ps < length)
    def _compute():
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        ok = kpos < length
        if window is not None:
            ok &= (qpos - kpos) < window
        if chunk is not None:
            ok &= (qpos // chunk) == (kpos // chunk)
        for h in range(n_kv):                  # static: Hkv is small
            q = q_ref[0, h].astype(jnp.float32) * scale       # (G, dh)
            k = k_ref[0, h].astype(jnp.float32)               # (ps, dh)
            v = v_ref[0, h].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(ok, s, NEG)                         # (G, ps)
            m_prev = m_ref[h]                                 # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_pg - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_kernel(q, k_pool, v_pool, pages, lengths, *,
                                  window=None, chunk=None, softcap=0.0,
                                  interpret=False):
    """q: (B,Hq,dh); pools: (n_pages, Hkv, page_size, dh); pages: (B,P)
    i32 physical-page table (entry 0 = the null page, only reachable past
    each row's length); lengths: (B,) live entries per row, each >= 1 and
    <= P * page_size -> (B,Hq,dh)."""
    B, Hq, dh = q.shape
    Hkv, ps = k_pool.shape[1], k_pool.shape[2]
    P = pages.shape[1]
    G = Hq // Hkv
    kernel = functools.partial(_kernel, scale=dh ** -0.5, window=window,
                               chunk=chunk, softcap=softcap, ps=ps, n_pg=P,
                               n_kv=Hkv)

    def kv_map(b, j, pt, lt):
        # block j of row b is whatever physical page the table names; past
        # the live length the last live page repeats, so no DMA is issued
        last = jnp.maximum(lt[b] - 1, 0) // ps
        return (pt[b, jnp.minimum(j, last)], 0, 0, 0)

    q_spec = pl.BlockSpec((1, Hkv, G, dh), lambda b, j, pt, lt: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, P),
        in_specs=[
            q_spec,
            pl.BlockSpec((1, Hkv, ps, dh), kv_map),
            pl.BlockSpec((1, Hkv, ps, dh), kv_map),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, dh), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(pages.astype(jnp.int32), lengths.astype(jnp.int32),
      q.reshape(B, Hkv, G, dh), k_pool, v_pool)
    return out.reshape(B, Hq, dh)


def paged_window_attention_kernel(q, k_pool, v_pool, pages, pos, *,
                                  window=None, chunk=None, softcap=0.0,
                                  interpret=False):
    """W-token window over the paged pool. q: (B,W,Hq,dh) holds W
    consecutive new positions per row whose KV is already in the pool;
    pos: (B,) each row's first new position. Offset w attends through
    pos + w, one 1-token kernel call per offset -> (B,W,Hq,dh)."""
    outs = [paged_decode_attention_kernel(
                q[:, w], k_pool, v_pool, pages, pos + w + 1, window=window,
                chunk=chunk, softcap=softcap, interpret=interpret)
            for w in range(q.shape[1])]
    return jnp.stack(outs, axis=1)
