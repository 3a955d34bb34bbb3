"""Decoder-only transformer substrate (dense / MoE / VLM-stub families).

Layers are stacked and scanned (small HLO, fast multi-pod compiles). All
functions are pure; sharding enters only through ``ShardCtx`` constraints so
the same code paths run on 1 CPU device (smoke tests) and on the 512-chip
dry-run mesh.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.kernels import ops as OPS
from repro.models import layers as L
from repro.models import moe as M
from repro.models.attention import blockwise_attention

LOSS_CHUNK = 1024


# ------------------------------------------------------------------ params

def init(key, cfg: ArchConfig):
    n_moe = 0
    n_dense = cfg.n_layers
    if cfg.moe is not None:
        n_moe = cfg.n_layers - cfg.moe.first_k_dense
        n_dense = cfg.moe.first_k_dense
    keys = jax.random.split(key, 8)
    d, dt = cfg.d_model, cfg.jdtype
    params = {
        "embed": L.ninit(keys[0], (cfg.vocab, d), dt, scale=1.0),
        "final_norm": L.oinit((d,), dt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.ninit(keys[1], (d, cfg.vocab), dt)

    def attn_block(key, n):
        ks = jax.random.split(key, 8)
        blk = {
            "ln1": L.oinit((n, d), dt),
            "wq": L.ninit(ks[0], (n, d, cfg.q_dim), dt),
            "wk": L.ninit(ks[1], (n, d, cfg.kv_dim), dt),
            "wv": L.ninit(ks[2], (n, d, cfg.kv_dim), dt),
            "wo": L.ninit(ks[3], (n, cfg.q_dim, d), dt),
            "ln2": L.oinit((n, d), dt),
        }
        if cfg.qkv_bias:
            blk["bq"] = L.zinit((n, cfg.q_dim), dt)
            blk["bk"] = L.zinit((n, cfg.kv_dim), dt)
            blk["bv"] = L.zinit((n, cfg.kv_dim), dt)
        return blk, ks[4]

    if n_dense:
        blk, k = attn_block(keys[2], n_dense)
        ff = cfg.d_ff
        if cfg.moe is not None:  # deepseek-style first-dense layer width
            ff = cfg.moe.d_ff_expert * (cfg.moe.top_k + cfg.moe.n_shared)
        blk.update(L.init_mlp(k, d, ff, cfg.mlp, dt, stacked=(n_dense,)))
        params["dense_layers"] = blk
    if n_moe:
        blk, k = attn_block(keys[3], n_moe)
        blk["moe"] = M.init_moe(k, cfg, stacked=(n_moe,))
        params["moe_layers"] = blk
    return params


def param_axes(cfg: ArchConfig):
    ax = {
        "embed": P("vocab", None),
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        ax["lm_head"] = P(None, "vocab")

    def attn_axes():
        blk = {
            "ln1": P(None, None),
            "wq": P(None, None, "qdim"),
            "wk": P(None, None, "kvdim"),
            "wv": P(None, None, "kvdim"),
            "wo": P(None, "qdim", None),
            "ln2": P(None, None),
        }
        if cfg.qkv_bias:
            blk["bq"] = P(None, "qdim")
            blk["bk"] = P(None, "kvdim")
            blk["bv"] = P(None, "kvdim")
        return blk

    if cfg.moe is None or cfg.moe.first_k_dense:
        blk = attn_axes()
        blk.update(L.mlp_axes(stacked=True))
        ax["dense_layers"] = blk
    if cfg.moe is not None:
        blk = attn_axes()
        blk["moe"] = M.moe_axes(cfg, stacked=True)
        ax["moe_layers"] = blk
    return ax


def abstract_params(cfg: ArchConfig):
    return jax.eval_shape(functools.partial(init, cfg=cfg), jax.random.PRNGKey(0))


# ----------------------------------------------------------------- helpers

def _constrain_qkv(ctx, cfg, q, k, v):
    if ctx is None:
        return q, k, v
    tp = ctx.axis_size("model")
    if cfg.n_heads % tp == 0:  # scheme A: Megatron head sharding
        q = ctx.constrain(q, "batch", None, "heads", None)
        k = ctx.constrain(k, "batch", None, "kv_heads", None)
        v = ctx.constrain(v, "batch", None, "kv_heads", None)
    else:                       # scheme B: sequence-sharded attention core
        q = ctx.constrain(q, "batch", "seq_tp", None, None)
        k = ctx.constrain(k, "batch", None, None, None)
        v = ctx.constrain(v, "batch", None, None, None)
    return q, k, v


def _attend_train(x, blk, cfg: ArchConfig, ctx, positions):
    """Self-attention sub-block (train/prefill path). x: (B, S, d)."""
    B, S, d = x.shape
    h = L.rms_norm(x, blk["ln1"], cfg.norm_eps)
    q = jnp.einsum("bsd,dq->bsq", h, blk["wq"].astype(h.dtype))
    k = jnp.einsum("bsd,dq->bsq", h, blk["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dq->bsq", h, blk["wv"].astype(h.dtype))
    if cfg.qkv_bias:
        q = q + blk["bq"].astype(h.dtype)
        k = k + blk["bk"].astype(h.dtype)
        v = v + blk["bv"].astype(h.dtype)
    q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    q, k, v = _constrain_qkv(ctx, cfg, q, k, v)
    out = blockwise_attention(
        q, k, v, causal=True, window=cfg.sliding_window, chunk=cfg.attn_chunk,
        q_positions=positions, kv_positions=positions,
        softcap=cfg.logit_softcap)
    out = out.reshape(B, S, cfg.q_dim)
    return jnp.einsum("bsq,qd->bsd", out, blk["wo"].astype(h.dtype))


def _block_train(x, blk, cfg: ArchConfig, ctx, positions, use_moe: bool):
    aux = jnp.zeros((), jnp.float32)
    x = x + _attend_train(x, blk, cfg, ctx, positions)
    h = L.rms_norm(x, blk["ln2"], cfg.norm_eps)
    if use_moe:
        ff, aux = M.moe_ffn(h, blk["moe"], cfg, ctx)
    else:
        ff = L.mlp_apply(h, blk["w_up"], blk["w_down"], cfg.mlp)
    x = x + ff
    if ctx is not None:
        x = ctx.constrain(x, "batch", "seq_tp", None)
    return x, aux


def _scan_blocks(x, stacked, cfg, ctx, positions, use_moe, remat: bool):
    body = functools.partial(_block_train, cfg=cfg, ctx=ctx,
                             positions=positions, use_moe=use_moe)
    if remat:
        body = jax.checkpoint(body)

    def step(carry, blk):
        x, aux = carry
        x, a = body(x, blk)
        return (x, aux + a), None

    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)), stacked)
    return x, aux


def chunked_xent(hidden, lm_head, labels, mask, ctx=None, chunk=LOSS_CHUNK):
    """Cross entropy streamed over sequence chunks; never materializes the
    full (B, S, V) logits. Returns (sum_nll, sum_mask)."""
    B, S, d = hidden.shape
    pad = (-S) % chunk
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    nb = (S + pad) // chunk
    hs = hidden.reshape(B, nb, chunk, d).swapaxes(0, 1)
    ls = labels.reshape(B, nb, chunk).swapaxes(0, 1)
    ms = mask.reshape(B, nb, chunk).swapaxes(0, 1)

    def step(carry, xs):
        h, lab, mk = xs
        logits = jnp.einsum("bsd,dv->bsv", h, lm_head.astype(h.dtype))
        if ctx is not None:
            logits = ctx.constrain(logits, "batch", None, "vocab")
        lf = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(lf, axis=-1)
        gold = jnp.take_along_axis(lf, lab[..., None], axis=-1)[..., 0]
        nll = (lse - gold + 1e-4 * jnp.square(lse)) * mk.astype(jnp.float32)
        s_nll, s_mask = carry
        return (s_nll + jnp.sum(nll), s_mask + jnp.sum(mk)), None

    (s_nll, s_mask), _ = jax.lax.scan(
        step, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, ls, ms))
    return s_nll, s_mask


# ------------------------------------------------------------------- train

def train_loss(params, batch, cfg: ArchConfig, ctx=None, remat=True):
    """batch: tokens (B,S), labels (B,S), mask (B,S) [, frontend (B,F,d)]."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    x = L.embed_lookup(params["embed"], tokens).astype(cfg.jdtype)
    labels, mask = batch["labels"], batch["mask"]
    if cfg.frontend is not None:
        pre = batch["frontend"].astype(cfg.jdtype)      # (B, F, d) stub embeds
        x = jnp.concatenate([pre, x], axis=1)
        labels = jnp.pad(labels, ((0, 0), (pre.shape[1], 0)))
        mask = jnp.pad(mask, ((0, 0), (pre.shape[1], 0)))
    if ctx is not None:
        x = ctx.constrain(x, "batch", "seq_tp", None)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None, :]

    aux_total = jnp.zeros((), jnp.float32)
    if "dense_layers" in params:
        x, aux = _scan_blocks(x, params["dense_layers"], cfg, ctx, positions,
                              use_moe=False, remat=remat)
        aux_total += aux
    if "moe_layers" in params:
        x, aux = _scan_blocks(x, params["moe_layers"], cfg, ctx, positions,
                              use_moe=True, remat=remat)
        aux_total += aux

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    s_nll, s_mask = chunked_xent(x, lm_head, labels, mask, ctx)
    loss = s_nll / jnp.maximum(s_mask, 1.0)
    if cfg.moe is not None:
        loss = loss + cfg.moe.router_aux_weight * aux_total / cfg.n_layers
    return loss


# ------------------------------------------------------------ prefill/decode

def _kv_proj(h, blk, cfg, positions):
    B, S = h.shape[:2]
    k = jnp.einsum("bsd,dq->bsq", h, blk["wk"].astype(h.dtype))
    v = jnp.einsum("bsd,dq->bsq", h, blk["wv"].astype(h.dtype))
    if cfg.qkv_bias:
        k = k + blk["bk"].astype(h.dtype)
        v = v + blk["bv"].astype(h.dtype)
    k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    return k, v


def prefill(params, tokens, cfg: ArchConfig, ctx=None, frontend=None):
    """Full-sequence prefill. Returns (last_logits (B,V), cache dict)."""
    B, S = tokens.shape
    x = L.embed_lookup(params["embed"], tokens).astype(cfg.jdtype)
    if cfg.frontend is not None and frontend is not None:
        x = jnp.concatenate([frontend.astype(cfg.jdtype), x], axis=1)
    if ctx is not None:
        x = ctx.constrain(x, "batch", "seq_tp", None)
    St = x.shape[1]
    positions = jnp.arange(St, dtype=jnp.int32)[None, :]

    caches = {}

    def run(stacked, use_moe, name):
        nonlocal x

        def step(carry, blk):
            xx = carry
            h = L.rms_norm(xx, blk["ln1"], cfg.norm_eps)
            q = jnp.einsum("bsd,dq->bsq", h, blk["wq"].astype(h.dtype))
            if cfg.qkv_bias:
                q = q + blk["bq"].astype(h.dtype)
            q = q.reshape(B, St, cfg.n_heads, cfg.head_dim)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k, v = _kv_proj(h, blk, cfg, positions)
            q, k, v = _constrain_qkv(ctx, cfg, q, k, v)
            out = blockwise_attention(
                q, k, v, causal=True, window=cfg.sliding_window,
                chunk=cfg.attn_chunk, q_positions=positions,
                kv_positions=positions, softcap=cfg.logit_softcap)
            out = out.reshape(B, St, cfg.q_dim)
            xx = xx + jnp.einsum("bsq,qd->bsd", out, blk["wo"].astype(h.dtype))
            h2 = L.rms_norm(xx, blk["ln2"], cfg.norm_eps)
            if use_moe:
                ff, _ = M.moe_ffn(h2, blk["moe"], cfg, ctx)
            else:
                ff = L.mlp_apply(h2, blk["w_up"], blk["w_down"], cfg.mlp)
            xx = xx + ff
            if ctx is not None:
                xx = ctx.constrain(xx, "batch", "seq_tp", None)
            return xx, (k, v)

        x, (ks, vs) = jax.lax.scan(step, x, stacked)
        caches[name] = {"k": ks, "v": vs}   # (L, B, St, kv, dh)

    if "dense_layers" in params:
        run(params["dense_layers"], False, "dense")
    if "moe_layers" in params:
        run(params["moe_layers"], True, "moe")

    xl = L.rms_norm(x[:, -1:, :], params["final_norm"], cfg.norm_eps)
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", xl, lm_head.astype(xl.dtype))[:, 0]
    if ctx is not None:
        logits = ctx.constrain(logits, "batch", "vocab")
    caches["pos"] = jnp.full((), St, jnp.int32)
    return logits, caches


def init_cache(cfg: ArchConfig, batch: int, max_len: int, ring: bool = False):
    """Zeroed decode cache. ``ring=True`` bounds the buffer for sub-quadratic
    archs (chunked attention -> attn_chunk slots; SWA -> window slots)."""
    slots = max_len
    if ring:
        if cfg.attn_chunk:
            slots = min(max_len, cfg.attn_chunk)
        elif cfg.sliding_window:
            slots = min(max_len, cfg.sliding_window)
    n_moe = 0 if cfg.moe is None else cfg.n_layers - cfg.moe.first_k_dense
    n_dense = cfg.n_layers - n_moe
    shape = lambda n: (n, batch, slots, cfg.n_kv_heads, cfg.head_dim)
    cache = {"pos": jnp.zeros((), jnp.int32)}
    if n_dense:
        cache["dense"] = {"k": jnp.zeros(shape(n_dense), cfg.jdtype),
                          "v": jnp.zeros(shape(n_dense), cfg.jdtype)}
    if n_moe:
        cache["moe"] = {"k": jnp.zeros(shape(n_moe), cfg.jdtype),
                        "v": jnp.zeros(shape(n_moe), cfg.jdtype)}
    return cache


def decode_step(params, token, cache, cfg: ArchConfig, ctx=None,
                unroll: bool = False, pages=None, kv_bucket=None,
                block_skip: int = 0):
    """One decode step. token: (B, 1) int32. Returns (logits (B,V), cache).

    ``cache["pos"]`` may be a scalar (whole batch in lockstep — the classic
    path) or a (B,) vector of per-row positions (the serving runtime's slot
    slab, where every row is an independent request at its own depth). All
    position arithmetic below broadcasts over the batch dim so both layouts
    share one trace.

    Paged layout: when ``pages`` ((B, P) int32 physical-page table) is
    given, per-layer caches are shared pools of fixed-size KV pages,
    head-major ((n_pages, kvh, page_size, dh)), instead of per-row slabs. The new
    token's KV scatters into the row's current page, and attention reads
    only the first ``kv_bucket`` logical entries (static, host-picked to
    cover the deepest live row) through ``kernels.ops`` — so decode cost
    tracks live tokens, not slab capacity. Physical page 0 is the null
    page: pad/retired rows point there, writes to it are never read.

    ``block_skip`` (dense layout only, opt-in — the serving runtime
    engages it per dispatch when live depth <= capacity/2): stream KV in
    blocks of that size, skipping blocks beyond every row's position at
    runtime. 0 = the single fused attention (default; fastest on a
    well-utilized cache, and what the legacy chunked path always uses).

    ``unroll=True`` replaces the layer scan with a static python loop:
    per-layer caches become independent aliased buffers (no stacked xs/ys
    round-trip through the while carry) — a serving-oriented layout that
    removes the full-cache read/convert/write per step (see EXPERIMENTS.md
    §Perf, yi-34b decode hillclimb)."""
    B = token.shape[0]
    pos = cache["pos"]
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))   # (B,)
    x = L.embed_lookup(params["embed"], token[:, 0])[:, None, :].astype(cfg.jdtype)
    positions = pos_b[:, None]                                    # (B, 1)
    paged = pages is not None
    if paged:
        pages = jnp.asarray(pages, jnp.int32)

    new_cache = {"pos": pos + 1}

    def run(stacked, kc, vc, use_moe):
        nonlocal x
        if paged:
            page_size = kc.shape[3]
            lp = pos_b // page_size                      # logical page
            off = pos_b % page_size                      # offset within it
            phys = jnp.take_along_axis(pages, lp[:, None], axis=1)[:, 0]
        else:
            slots = kc.shape[2]
            slot = pos_b % slots           # (B,) ring write for bounded caches

        def step(carry, xs):
            xx = carry
            blk, k_l, v_l = xs
            h = L.rms_norm(xx, blk["ln1"], cfg.norm_eps)
            q = jnp.einsum("bsd,dq->bsq", h, blk["wq"].astype(h.dtype))
            if cfg.qkv_bias:
                q = q + blk["bq"].astype(h.dtype)
            q = q.reshape(B, 1, cfg.n_heads, cfg.head_dim)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k, v = _kv_proj(h, blk, cfg, positions)
            if paged:
                # scatter the new KV into each row's current physical page
                k_l = k_l.at[phys, :, off].set(k[:, 0].astype(k_l.dtype))
                v_l = v_l.at[phys, :, off].set(v[:, 0].astype(v_l.dtype))
                out = OPS.decode_attention_paged(
                    q, k_l, v_l, pages, pos_b + 1, kv_bucket=kv_bucket,
                    page_size=page_size, window=cfg.sliding_window,
                    chunk=cfg.attn_chunk, softcap=cfg.logit_softcap, ctx=ctx)
            else:
                # explicit masked write instead of dynamic_update_slice: on
                # a slot-sharded cache GSPMD lowers DUS to a masked select
                # anyway, but routes it through f32; the where() stays in
                # cache dtype and fully local (EXPERIMENTS.md §Perf).
                wmask = (jnp.arange(slots, dtype=jnp.int32)[None, :]
                         == slot[:, None])[:, :, None, None]
                k_l = jnp.where(wmask, k.astype(k_l.dtype), k_l)
                v_l = jnp.where(wmask, v.astype(v_l.dtype), v_l)
                # absolute positions of cache slots (ring-aware); unwritten
                # slots get INT32_MAX so the kv_len mask rejects them.
                slot_ids = jnp.arange(slots, dtype=jnp.int32)[None, :]
                wraps = ((pos_b // slots) * slots)[:, None]
                abs_pos = jnp.where(slot_ids <= slot[:, None],
                                    wraps + slot_ids,
                                    wraps - slots + slot_ids)
                kv_pos = jnp.where(abs_pos >= 0, abs_pos,
                                   jnp.iinfo(jnp.int32).max)
                out = OPS.decode_attention_model(
                    q, k_l, v_l, pos=pos, window=cfg.sliding_window,
                    chunk=cfg.attn_chunk, kv_positions=kv_pos,
                    softcap=cfg.logit_softcap,
                    block_skip=block_skip or None)
            out = out.reshape(B, 1, cfg.q_dim)
            xx = xx + jnp.einsum("bsq,qd->bsd", out, blk["wo"].astype(h.dtype))
            h2 = L.rms_norm(xx, blk["ln2"], cfg.norm_eps)
            if use_moe:
                ff, _ = M.moe_ffn(h2, blk["moe"], cfg, ctx)
            else:
                ff = L.mlp_apply(h2, blk["w_up"], blk["w_down"], cfg.mlp)
            xx = xx + ff
            return xx, (k_l, v_l)

        if unroll:
            nl = kc.shape[0]
            ks_new, vs_new = kc, vc
            for l in range(nl):
                blk_l = jax.tree.map(lambda a: a[l], stacked)
                x, (k_l, v_l) = step(x, (blk_l, kc[l], vc[l]))
                ks_new = ks_new.at[l].set(k_l)
                vs_new = vs_new.at[l].set(v_l)
            return {"k": ks_new, "v": vs_new}
        x, (ks, vs) = jax.lax.scan(step, x, (stacked, kc, vc))
        return {"k": ks, "v": vs}

    if "dense_layers" in params:
        new_cache["dense"] = run(params["dense_layers"], cache["dense"]["k"],
                                 cache["dense"]["v"], False)
    if "moe_layers" in params:
        new_cache["moe"] = run(params["moe_layers"], cache["moe"]["k"],
                               cache["moe"]["v"], True)

    xl = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", xl, lm_head.astype(xl.dtype))[:, 0]
    if ctx is not None:
        logits = ctx.constrain(logits, "batch", "vocab")
    return logits, new_cache


def decode_window(params, tokens, cache, cfg: ArchConfig, ctx=None, *,
                  pages, pos, kv_bucket):
    """W-token decode window over the paged cache. tokens: (B, W) int32;
    pos: (B,) each row's first new position. Writes KV for all W tokens at
    positions pos..pos+W-1 and returns logits at every offset ((B, W, V))
    — the prefix-cache tail prefill reads only the last offset's argmax,
    the speculative verify step reads all of them to decide acceptance.
    ``cache["pos"]`` is deliberately NOT advanced: the caller owns
    position state (a verify dispatch may reject most of the window).
    Write targets must be CoW-private (the runtime copies shared pages
    first); pad rows point at null page 0, written but never read."""
    B, W = tokens.shape
    pages = jnp.asarray(pages, jnp.int32)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (B,))
    positions = pos_b[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
    x = L.embed_lookup(params["embed"], tokens).astype(cfg.jdtype)

    new_cache = {"pos": cache["pos"]}

    def run(stacked, kc, vc, use_moe):
        nonlocal x
        page_size = kc.shape[3]
        lp = positions // page_size                     # (B, W) logical page
        off = positions % page_size
        phys = jnp.take_along_axis(pages, lp, axis=1)   # (B, W) physical

        def step(carry, xs):
            xx = carry
            blk, k_l, v_l = xs
            h = L.rms_norm(xx, blk["ln1"], cfg.norm_eps)
            q = jnp.einsum("bsd,dq->bsq", h, blk["wq"].astype(h.dtype))
            if cfg.qkv_bias:
                q = q + blk["bq"].astype(h.dtype)
            q = q.reshape(B, W, cfg.n_heads, cfg.head_dim)
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k, v = _kv_proj(h, blk, cfg, positions)
            k_l = k_l.at[phys, :, off].set(k.astype(k_l.dtype))
            v_l = v_l.at[phys, :, off].set(v.astype(v_l.dtype))
            out = OPS.window_attention_paged(
                q, k_l, v_l, pages, pos_b, kv_bucket=kv_bucket,
                page_size=page_size, window=cfg.sliding_window,
                chunk=cfg.attn_chunk, softcap=cfg.logit_softcap, ctx=ctx)
            out = out.reshape(B, W, cfg.q_dim)
            xx = xx + jnp.einsum("bsq,qd->bsd", out, blk["wo"].astype(h.dtype))
            h2 = L.rms_norm(xx, blk["ln2"], cfg.norm_eps)
            if use_moe:
                ff, _ = M.moe_ffn(h2, blk["moe"], cfg, ctx)
            else:
                ff = L.mlp_apply(h2, blk["w_up"], blk["w_down"], cfg.mlp)
            xx = xx + ff
            return xx, (k_l, v_l)

        x, (ks, vs) = jax.lax.scan(step, x, (stacked, kc, vc))
        return {"k": ks, "v": vs}

    if "dense_layers" in params:
        new_cache["dense"] = run(params["dense_layers"], cache["dense"]["k"],
                                 cache["dense"]["v"], False)
    if "moe_layers" in params:
        new_cache["moe"] = run(params["moe_layers"], cache["moe"]["k"],
                               cache["moe"]["v"], True)

    xl = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    lm_head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", xl, lm_head.astype(xl.dtype))
    if ctx is not None:
        logits = ctx.constrain(logits, "batch", None, "vocab")
    return logits, new_cache
