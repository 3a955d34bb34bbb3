"""Pallas TPU paged decode attention (vLLM-style PagedAttention).

The KV cache is a shared physical pool of fixed-size pages, stored
head-major: ``(n_pages, Hkv, page_size, dh)`` per layer. Each batch row
owns a page table mapping its logical pages to physical pages (page 0 is
the null page, reachable only past a row's length).

One grid step covers ``ppb`` consecutive logical pages of a row, a block
of ``ppb * page_size`` entries, so the fixed cost of a step and the
matmuls are spread over many pages. ``ppb`` follows from the shapes the
kernel sees (``pages_per_block``): the fewest pages whose K block reaches
``BLOCK_BYTES``, at most the ``P`` pages a row spans. Under ``shard_map``
the page bytes are the shard's own.

The grid is ``(B, ceil(P / ppb))``. The pools stay where they are
(``pl.ANY``); step ``(b, j)`` copies the physical pages of block ``j`` of
row ``b`` into VMEM scratch ``(2, Hkv, ppb * page_size, dh)`` with
``make_async_copy``, the page ids read from the scalar-prefetched table.
Only pages below the row's live length are copied. The two scratch slots
double-buffer: while a block is computed, the next live block is already
being fetched, the next row's first block at a row's last live step.
Blocks wholly past a row's length start neither copy nor compute.

The row's query rides in as a ``(1, Hkv, G, dh)`` block (the
``G = Hq // Hkv`` query heads of each KV group side by side), so each page
is fetched once for all the query heads that read it. Per block and KV
head the math is one ``(G, ppb * page_size)`` score tile and one
``(G, dh)`` accumulate of a streaming log-sum-exp, in float32 with K and
V upcast from storage. Masking is per token and matches the jnp path:
causal by length, sliding window, chunked, logit soft-cap. Entries past
the length hold whatever the scratch held before; their scores are masked
and their V rows zeroed, so none of it reaches the accumulator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
BLOCK_BYTES = 512 * 1024       # K bytes one grid step fetches, at least


def pages_per_block(page_bytes: int, n_pages: int) -> int:
    """Pages one grid step covers: the fewest whose K block reaches
    ``BLOCK_BYTES``, at least 1 and at most the row's ``n_pages``."""
    return max(1, min(n_pages, -(-BLOCK_BYTES // page_bytes)))


def _kernel(pages_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
            scale, window, chunk, softcap, ps, ppb, n_pg, n_blk, n_kv):
    b = pl.program_id(0)
    j = pl.program_id(1)
    rows = pl.num_programs(0)
    bk = ppb * ps

    def depth(row):
        # a retired row keeps its last depth over the null page, which
        # may pass the bucket: it reads no further than its table
        return jnp.minimum(len_ref[row], n_pg * ps)

    def live_blocks(row):                    # block 0 always counts
        return jnp.maximum((depth(row) + bk - 1) // bk, 1)

    def page_copies(row, blk, slot, act):
        """``act`` on the K and V copy of each page of block ``blk`` of
        ``row`` that lies below the row's length. A loop, not ``ppb``
        unrolled copies: every decode program lowers this body, and its
        size is set-up time."""
        n_live = jnp.clip((depth(row) + ps - 1) // ps - blk * ppb, 0, ppb)

        def one_page(i, carry):
            page = pages_ref[row, blk * ppb + i]
            at = pl.ds(pl.multiple_of(i * ps, ps), ps)
            for kv, (pool, buf) in enumerate(((k_hbm, k_buf),
                                              (v_hbm, v_buf))):
                act(pltpu.make_async_copy(pool.at[page],
                                          buf.at[slot, :, at],
                                          sems.at[kv, slot]))
            return carry

        jax.lax.fori_loop(0, n_live, one_page, 0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j < live_blocks(b))
    def _compute():
        @pl.when((b == 0) & (j == 0))
        def _first():
            slot_ref[0] = 0
            page_copies(0, 0, 0, lambda c: c.start())

        slot = slot_ref[0]
        row_done = j + 1 >= live_blocks(b)
        nxt_b = jnp.where(row_done, b + 1, b)
        nxt_j = jnp.where(row_done, 0, j + 1)

        @pl.when(nxt_b < rows)
        def _prefetch():
            page_copies(nxt_b, nxt_j, 1 - slot, lambda c: c.start())
            slot_ref[0] = 1 - slot

        page_copies(b, j, slot, lambda c: c.wait())
        length = depth(b)
        qpos = length - 1
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        ok = kpos < length
        if window is not None:
            ok &= (qpos - kpos) < window
        if chunk is not None:
            ok &= (qpos // chunk) == (kpos // chunk)
        held = (j * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
                < length)
        for h in range(n_kv):                  # static: Hkv is small
            q = q_ref[0, h].astype(jnp.float32) * scale       # (G, dh)
            k = k_buf[slot, h].astype(jnp.float32)            # (bk, dh)
            v = jnp.where(held, v_buf[slot, h].astype(jnp.float32), 0.0)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if softcap:
                s = jnp.tanh(s / softcap) * softcap
            s = jnp.where(ok, s, NEG)                         # (G, bk)
            m_prev = m_ref[h]                                 # (G, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == n_blk - 1)
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
    ppb = pages_per_block(Hkv * ps * dh * k_pool.dtype.itemsize, P)
    n_blk = -(-P // ppb)
    kernel = functools.partial(_kernel, scale=dh ** -0.5, window=window,
                               chunk=chunk, softcap=softcap, ps=ps, ppb=ppb,
                               n_pg=P, n_blk=n_blk, n_kv=Hkv)
    q_spec = pl.BlockSpec((1, Hkv, G, dh), lambda b, j, pt, lt: (b, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_blk),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, Hkv, ppb * ps, dh), k_pool.dtype),
            pltpu.VMEM((2, Hkv, ppb * ps, dh), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),      # (K|V, slot)
            pltpu.SMEM((1,), jnp.int32),          # slot of the current block
            pltpu.VMEM((Hkv, G, dh), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
            pltpu.VMEM((Hkv, G, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, dh), q.dtype),
        # a block's prefetch reaches into the next row: rows run in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
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
