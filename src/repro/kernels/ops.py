"""Jit'd dispatch wrappers over the Pallas kernels + the kernel-mode toggle.

On TPU the kernels run natively; on CPU (this container) they run in
interpret mode when requested, otherwise the jnp fallbacks from
repro.models are used (that is also what the dry-run lowers). The model
layer routes its decode hot path through ``decode_attention_model`` /
``decode_attention_paged`` below, which honor the mode toggle:

  KERNEL_MODE=auto    pick per backend: Pallas on TPU, jnp elsewhere
  KERNEL_MODE=pallas  force the Pallas kernels (interpret mode off-TPU —
                      slow on CPU, meant for parity testing)
  KERNEL_MODE=jnp     force the jnp paths (block-skip streaming decode)

Set via the ``KERNEL_MODE`` env var or ``set_kernel_mode()`` (the serve
driver's ``--kernel-mode`` flag). The mode is read at *trace* time, so
flip it before building jitted closures (RuntimeKernels / ElasticServing
cache compiled artifacts keyed by shape, not by mode).
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_scan import mlstm_chunkwise_kernel
from repro.kernels.paged_decode_attention import (
    paged_decode_attention_kernel, paged_window_attention_kernel)
from repro.kernels.ssm_scan import ssm_scan_kernel

KERNEL_MODES = ("auto", "pallas", "jnp")
_kernel_mode = None                     # None -> read KERNEL_MODE env var


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not on_tpu()


def set_kernel_mode(mode: str | None) -> None:
    """Override the kernel dispatch mode (None -> back to the env var)."""
    global _kernel_mode
    if mode is not None and mode not in KERNEL_MODES:
        raise ValueError(f"kernel mode {mode!r} not in {KERNEL_MODES}")
    _kernel_mode = mode


def kernel_mode() -> str:
    """The configured mode (may be "auto")."""
    if _kernel_mode is not None:
        return _kernel_mode
    env = os.environ.get("KERNEL_MODE", "auto")
    return env if env in KERNEL_MODES else "auto"


def resolved_mode() -> str:
    """The effective implementation choice: "pallas" or "jnp"."""
    mode = kernel_mode()
    if mode == "auto":
        return "pallas" if on_tpu() else "jnp"
    return mode


def use_kernels() -> bool:
    """True when the model layer should route through the Pallas kernels."""
    return resolved_mode() == "pallas"


# ------------------------------------------------------- model-layer dispatch

def decode_attention_model(q, k_cache, v_cache, *, pos, window=None,
                           chunk=None, kv_positions=None, softcap=0.0,
                           block_skip=None):
    """Decode attention for the dense (slab / grow_cache) layout.

    q: (B,1,Hq,dh); caches: (B,Smax,Hkv,dh); pos scalar or (B,). The ring
    layouts (``kv_positions`` carrying absolute positions) have no Pallas
    kernel, so this always lowers the jnp path. ``block_skip`` (opt-in;
    the serving runtime engages it per dispatch) streams KV in blocks and
    skips blocks beyond the deepest live row at runtime — the default
    stays the single fused attention, which wins on a well-utilized
    cache. Called inside jitted model code: choices bake at trace time.
    """
    from repro.models.attention import decode_attention
    return decode_attention(q, k_cache, v_cache, pos=pos, window=window,
                            chunk=chunk, kv_positions=kv_positions,
                            softcap=softcap, block_skip=block_skip)


def _paged_kernel_call(kernel, q, k_pool, v_pool, pages, lengths, ctx,
                       **kw):
    """Run a paged Pallas kernel, on a mesh one shard per device.

    GSPMD cannot partition a ``pallas_call``, so on a mesh the kernel runs
    under ``shard_map``: the pool's KV-head axis (and the query heads of
    each KV group with it) is split the way the pool is committed
    (``model_api.paged_cache_axes``), and the batch rows (queries, page
    table, lengths) the way decode activations are (``"batch"``). Each
    device then attends its own rows over its own KV heads; no collective
    is needed."""
    call = functools.partial(kernel, interpret=_interpret(), **kw)
    if ctx is None or ctx.mesh is None:
        return call(q, k_pool, v_pool, pages, lengths)
    from jax.sharding import PartitionSpec as P
    pool = ctx.spec((None, "kv_heads"), k_pool.shape)
    kv_ax = pool[1] if len(pool) > 1 else None
    rows = ctx.spec(("batch",), q.shape[:1])
    b_ax = rows[0] if len(rows) else None
    q_spec = P(b_ax, *([None] * (q.ndim - 3)), kv_ax, None)
    pool_spec = P(None, kv_ax)
    return jax.shard_map(call, mesh=ctx.mesh,
                         in_specs=(q_spec, pool_spec, pool_spec, P(b_ax),
                                   P(b_ax)),
                         out_specs=q_spec, check_vma=False)(
        q, k_pool, v_pool, pages, lengths)


def _gather_pages(pool, pages, kv_bucket):
    """First kv_bucket entries of each row, gathered from the head-major
    pool: (n_pages, Hkv, ps, dh) -> (B, kv_bucket, Hkv, dh)."""
    B = pages.shape[0]
    g = pool[pages].transpose(0, 1, 3, 2, 4)      # (B, npg, ps, Hkv, dh)
    return g.reshape(B, kv_bucket, pool.shape[1], pool.shape[3])


def decode_attention_paged(q, k_pool, v_pool, pages, lengths, *, kv_bucket,
                           page_size, window=None, chunk=None, softcap=0.0,
                           ctx=None):
    """Decode attention for the paged layout.

    q: (B,1,Hq,dh); pools: (n_pages, Hkv, page_size, dh), head-major;
    pages: (B,P) physical-page table; lengths: (B,) live entries per row.
    ``kv_bucket`` (static, a multiple of page_size) bounds how many
    *logical* entries are read — the host picks the smallest bucket
    covering the deepest live row, so read cost tracks live tokens, not
    capacity. ``ctx`` carries the serving mesh (see
    ``_paged_kernel_call``).

    pallas mode: the paged kernel reads the bucket's pages straight from
    the pool via scalar-prefetch indexing (no gather) and skips each
    row's pages past its length. jnp mode: gather the bucket's pages per
    row and run the block-skip streaming decode over them.
    """
    npg = kv_bucket // page_size
    pid = pages[:, :npg]                                   # (B, npg)
    if use_kernels():
        out = _paged_kernel_call(paged_decode_attention_kernel, q[:, 0],
                                 k_pool, v_pool, pid, lengths, ctx,
                                 window=window, chunk=chunk, softcap=softcap)
        return out[:, None]
    from repro.models.attention import decode_attention
    kb = _gather_pages(k_pool, pid, kv_bucket)
    vb = _gather_pages(v_pool, pid, kv_bucket)
    # the gathered width is already bucketed to the deepest live row, so
    # intra-bucket skipping only pays once the bucket spans several pages
    skip = page_size if npg >= 4 else None
    return decode_attention(q, kb, vb, pos=lengths - 1, window=window,
                            chunk=chunk, softcap=softcap, block_skip=skip)


def window_attention_paged(q, k_pool, v_pool, pages, pos, *, kv_bucket,
                           page_size, window=None, chunk=None, softcap=0.0,
                           ctx=None):
    """W-token decode-window attention for the paged layout.

    q: (B,W,Hq,dh) — W consecutive new positions per row, whose KV the
    caller already scattered into the pool at positions pos..pos+W-1;
    pos: (B,) each row's first new position. Serves the prefix-cache tail
    prefill and the speculative-decode verify dispatch.

    pallas mode: ``paged_window_attention_kernel`` (one 1-token kernel
    call per window offset; offset w attends through pos+w). jnp mode:
    one page gather + blockwise attention with per-offset causal masking
    over the kv_bucket.
    """
    npg = kv_bucket // page_size
    pid = pages[:, :npg]                                   # (B, npg)
    if use_kernels():
        return _paged_kernel_call(paged_window_attention_kernel, q, k_pool,
                                  v_pool, pid, pos, ctx, window=window,
                                  chunk=chunk, softcap=softcap)
    from repro.models.attention import blockwise_attention
    B, W = q.shape[:2]
    kb = _gather_pages(k_pool, pid, kv_bucket)
    vb = _gather_pages(v_pool, pid, kv_bucket)
    q_pos = pos[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    kv_pos = jnp.broadcast_to(
        jnp.arange(kv_bucket, dtype=jnp.int32)[None, :], (B, kv_bucket))
    return blockwise_attention(q, kb, vb, causal=True, window=window,
                               chunk=chunk, q_positions=q_pos,
                               kv_positions=kv_pos, softcap=softcap)


# --------------------------------------------------------- jit'd kernel entry

@functools.partial(jax.jit, static_argnames=("causal", "window", "chunk",
                                             "softcap", "block_q", "block_k"))
def attention(q, k, v, *, causal=True, window=None, chunk=None, softcap=0.0,
              block_q=128, block_k=128):
    """q: (B,S,Hq,dh) model layout -> flash kernel layout and back."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = flash_attention(qt, kt, vt, causal=causal, window=window,
                          chunk=chunk, softcap=softcap, block_q=block_q,
                          block_k=block_k, interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("window", "chunk", "block_k"))
def decode_attention(q, k_cache, v_cache, lengths, *, window=None,
                     chunk=None, block_k=512):
    """q: (B,1,Hq,dh) -> (B,1,Hq,dh)."""
    out = decode_attention_kernel(q[:, 0], k_cache, v_cache, lengths,
                                  window=window, chunk=chunk,
                                  block_k=block_k, interpret=_interpret())
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm(q, k, v, li, lf, *, chunk=64):
    return mlstm_chunkwise_kernel(q, k, v, li, lf, chunk=chunk,
                                  interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "block_di"))
def ssm(u, dt, A, Bsel, Csel, Dskip, *, chunk=64, block_di=256):
    return ssm_scan_kernel(u, dt, A, Bsel, Csel, Dskip, chunk=chunk,
                           block_di=block_di, interpret=_interpret())
