"""Uniform model protocol over the four family implementations.

Every family exposes: init / abstract_params / param_axes / train_loss /
prefill / decode_step / init_cache. This module adds input/cache spec
builders (ShapeDtypeStruct stand-ins, no allocation) used by smoke tests,
the launcher, and the multi-pod dry-run.
"""
from __future__ import annotations

import functools
from types import ModuleType

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig, get_config
from repro.models import encdec, hybrid, transformer, xlstm


def get_module(cfg: ArchConfig) -> ModuleType:
    if cfg.family == "encdec":
        return encdec
    if cfg.family == "hybrid":
        return hybrid
    if cfg.family == "ssm" and cfg.xlstm is not None:
        return xlstm
    return transformer          # dense / moe / vlm


# ------------------------------------------------------------- input specs

def batch_specs(cfg: ArchConfig, shape: ShapeConfig):
    """Training-batch ShapeDtypeStructs + logical axes."""
    B, S = shape.global_batch, shape.seq_len
    specs = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "mask": jax.ShapeDtypeStruct((B, S), jnp.float32),
    }
    axes = {
        "tokens": P("batch", None),
        "labels": P("batch", None),
        "mask": P("batch", None),
    }
    if cfg.frontend is not None:
        specs["frontend"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_seq, cfg.d_model), jnp.float32)
        axes["frontend"] = P("batch", None, None)
    return specs, axes


def prefill_specs(cfg: ArchConfig, shape: ShapeConfig):
    B, S = shape.global_batch, shape.seq_len
    specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    axes = {"tokens": P("batch", None)}
    if cfg.frontend is not None:
        specs["frontend"] = jax.ShapeDtypeStruct(
            (B, cfg.frontend_seq, cfg.d_model), jnp.float32)
        axes["frontend"] = P("batch", None, None)
    return specs, axes


def cache_specs(cfg: ArchConfig, shape: ShapeConfig, cache_mode="slots"):
    """Abstract decode cache (ring-bounded for long_500k) + logical axes."""
    mod = get_module(cfg)
    ring = shape.name == "long_500k"
    abstract = jax.eval_shape(functools.partial(
        mod.init_cache, cfg, shape.global_batch, shape.seq_len, ring=ring))
    return abstract, cache_axes(cfg, cache_mode)


def cache_axes(cfg: ArchConfig, cache_mode: str = "slots"):
    """cache_mode: "slots" shards the KV slot dim (spreads memory; GSPMD
    lowers the per-token write to a masked local-slice rewrite); "dh" shards
    head_dim (local one-slot writes; reads psum score stats). See
    EXPERIMENTS.md §Perf (yi-34b decode)."""
    if cache_mode == "dh":
        kv = {"k": P(None, "batch", None, None, "inner"),
              "v": P(None, "batch", None, None, "inner")}
    else:
        kv = {"k": P(None, "batch", "cache_seq"),
              "v": P(None, "batch", "cache_seq")}
    if cfg.family == "encdec":
        return {"self": dict(kv), "cross": dict(kv), "pos": P()}
    if cfg.family == "hybrid":
        return {**kv, "ssm": P(None, "batch", "inner"),
                "conv": P(None, "batch", None, "inner"), "pos": P()}
    if cfg.xlstm is not None:
        return {
            "mlstm": (P(None, None, "batch", None, None, "inner"),
                      P(None, None, "batch", None, "inner"),
                      P(None, None, "batch"),
                      P(None, None, "batch", None, "inner")),
            "slstm": (P(None, "batch"), P(None, "batch"), P(None, "batch"),
                      P(None, "batch"), P(None, "batch", None, None)),
            "pos": P(),
        }
    out = {"pos": P()}
    if cfg.moe is None or cfg.moe.first_k_dense:
        out["dense"] = dict(kv)
    if cfg.moe is not None:
        out["moe"] = dict(kv)
    return out


def decode_token_specs(cfg: ArchConfig, shape: ShapeConfig):
    B = shape.global_batch
    return (jax.ShapeDtypeStruct((B, 1), jnp.int32), P("batch", None))


def grow_cache(cfg: ArchConfig, cache, max_len: int):
    """Pad prefill-produced KV caches (seq dim) to ``max_len`` slots so decode
    can continue past the prefill length. Recurrent states are size-invariant."""
    def pad_kv(d):
        out = {}
        for name in ("k", "v"):
            buf = d[name]
            slots = buf.shape[2]
            if slots < max_len:
                buf = jnp.pad(buf, ((0, 0), (0, 0), (0, max_len - slots),
                                    (0, 0), (0, 0)))
            out[name] = buf
        return out

    if cfg.family == "encdec":
        return {"self": pad_kv(cache["self"]), "cross": cache["cross"],
                "pos": cache["pos"]}
    if cfg.family == "hybrid":
        new = dict(cache)
        new.update(pad_kv(cache))
        return new
    if cfg.xlstm is not None:
        return cache
    new = dict(cache)
    for part in ("dense", "moe"):
        if part in cache:
            new[part] = pad_kv(cache[part])
    return new


# ------------------------------------------------- serving runtime helpers

def supports_slots(cfg: ArchConfig) -> bool:
    """True when the family's decode cache is a pure KV slab whose rows are
    independent requests (dense / moe / vlm -> transformer module). The
    recurrent families (hybrid, xlstm) and encdec carry scalar-position
    states the slot runtime cannot address per-row yet."""
    return get_module(cfg) is transformer


def pow2_bucket(n: int, lo: int, hi: int) -> int:
    """Smallest power-of-two >= n, clamped to [lo, hi]. Padding shapes to
    these buckets bounds the number of distinct jit traces to O(log)."""
    b = lo
    while b < n and b < hi:
        b *= 2
    return min(max(b, lo), hi)


def bucket_ladder(lo: int, hi: int):
    out, b = [], lo
    while b < hi:
        out.append(b)
        b *= 2
    out.append(hi)
    return tuple(out)


def init_slab_cache(cfg: ArchConfig, slots: int, capacity: int):
    """Fixed-shape slot-slab decode cache: ``slots`` independent requests x
    ``capacity`` KV entries each, with a per-row position vector (the shape
    never changes across admissions, so decode compiles exactly once)."""
    cache = get_module(cfg).init_cache(cfg, slots, capacity)
    cache["pos"] = jnp.zeros((slots,), jnp.int32)
    return cache


def scatter_prefill(cfg: ArchConfig, slab, prefill_cache, slot_idx, seq_len):
    """Write a prefilled (B, seq_len) KV cache into slab rows ``slot_idx``
    ((B,) int32) and stamp their positions. Pure function of fixed shapes —
    jit it once per (batch-bucket, length-bucket)."""
    new = dict(slab)
    for part in ("dense", "moe"):
        if part not in prefill_cache or part not in slab:
            continue
        dst = dict(slab[part])
        for nm in ("k", "v"):
            src = prefill_cache[part][nm]          # (L, B, S, kvh, dh)
            dst[nm] = slab[part][nm].at[:, slot_idx, :src.shape[2]].set(
                src.astype(slab[part][nm].dtype))
        new[part] = dst
    new["pos"] = slab["pos"].at[slot_idx].set(jnp.int32(seq_len))
    return new


def init_paged_cache(cfg: ArchConfig, rows: int, n_pages: int,
                     page_size: int):
    """Paged decode cache: a shared physical pool of ``n_pages`` KV pages
    of ``page_size`` entries per layer, head-major
    ((L, n_pages, kvh, page_size, dh) — the block layout the paged Pallas
    kernel reads), plus a per-row position vector for ``rows`` slots.
    Which pages a row owns lives host-side (the runtime's page table /
    allocator); physical page 0 is reserved as the null page. HBM scales
    with the pool, not with rows x capacity."""
    # the module's slab cache, one "row" per page: (L, n_pages, ps, kvh, dh)
    slab = get_module(cfg).init_cache(cfg, n_pages, page_size)
    cache = {part: {nm: kv.swapaxes(2, 3) for nm, kv in slab[part].items()}
             for part in ("dense", "moe") if part in slab}
    cache["pos"] = jnp.zeros((rows,), jnp.int32)
    return cache


def paged_cache_axes(cfg: ArchConfig):
    """Logical axes of ``init_paged_cache``: the pool splits over KV heads
    (each chip of a tensor-parallel replica holds its heads' pages, which
    is what the paged kernel's ``shard_map`` expects); positions are
    replicated."""
    shapes = jax.eval_shape(lambda: init_paged_cache(cfg, 1, 1, 1))
    axes = jax.tree.map(lambda _: P(None, None, "kv_heads"), shapes)
    axes["pos"] = P()
    return axes


def scatter_prefill_paged(cfg: ArchConfig, slab, prefill_cache, slot_idx,
                          seq_len, page_rows, page_size: int):
    """Paged counterpart of ``scatter_prefill``: split a prefilled
    (B, seq_len) KV cache into page-size chunks and scatter them into the
    physical pool pages named by ``page_rows`` ((B, ceil(seq_len/page))
    int32), stamping positions for rows ``slot_idx``. Pad rows aim all
    their chunks at the null page (0) — colliding writes there are never
    read. Pure function of fixed shapes, jitted once per bucket."""
    new = dict(slab)
    npg = page_rows.shape[1]
    flat = page_rows.reshape(-1)
    for part in ("dense", "moe"):
        if part not in prefill_cache or part not in slab:
            continue
        dst = dict(slab[part])
        for nm in ("k", "v"):
            src = prefill_cache[part][nm]          # (L, B, S, kvh, dh)
            L, B, S, kvh, dh = src.shape
            pad = npg * page_size - S
            if pad:
                src = jnp.pad(src, ((0, 0), (0, 0), (0, pad),
                                    (0, 0), (0, 0)))
            src = src.reshape(L, B * npg, page_size, kvh, dh)
            dst[nm] = slab[part][nm].at[:, flat].set(
                src.swapaxes(2, 3).astype(slab[part][nm].dtype))
        new[part] = dst
    new["pos"] = slab["pos"].at[slot_idx].set(jnp.int32(seq_len))
    return new


def fused_decode(params, tok, cache, active, remaining, cfg: ArchConfig,
                 ctx=None, steps: int = 8, pages=None, kv_bucket=None,
                 block_skip=None):
    """``steps`` greedy decode steps fused into one ``lax.scan`` (one device
    dispatch per block instead of per token). Rows where ``active`` is False
    are frozen: their position does not advance and their token does not
    change, so finished requests stop paying for rides they do not take.

    tok: (S, 1) int32; active: (S,) bool; remaining: (S,) int32.
    ``pages``/``kv_bucket`` select the paged-cache layout (transformer
    only): the page table is constant across the fused block — the host
    pre-allocates pages covering every row's position through the final
    step — and ``kv_bucket`` must cover max(pos) + steps.
    Returns (tok, cache, active, remaining, tokens (steps, S), the last
    step's logits (S, V) in float32)."""
    mod = get_module(cfg)
    kw = {} if pages is None else {"pages": pages, "kv_bucket": kv_bucket}
    if block_skip is not None:       # 0 = force the plain full-width path
        kw["block_skip"] = block_skip

    def step(carry, _):
        tok, cache, active, remaining, _ = carry
        pos0 = cache["pos"]
        logits, cache = mod.decode_step(params, tok, cache, cfg, ctx, **kw)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        tok = jnp.where(active[:, None], nxt, tok)
        cache["pos"] = jnp.where(active, cache["pos"], pos0)
        remaining = remaining - active.astype(jnp.int32)
        active = active & (remaining > 0)
        return ((tok, cache, active, remaining, logits.astype(jnp.float32)),
                nxt[:, 0])

    last = jnp.zeros((tok.shape[0], cfg.vocab), jnp.float32)
    (tok, cache, active, remaining, last), toks = jax.lax.scan(
        step, (tok, cache, active, remaining, last), None, length=steps)
    return tok, cache, active, remaining, toks, last


def decode_window(params, tokens, cache, cfg: ArchConfig, ctx=None, *,
                  pages, pos, kv_bucket):
    """Multi-token decode window (paged transformer slab only): write KV
    for all W tokens at positions pos..pos+W-1 and return per-offset
    logits ((B, W, V)) without advancing cache positions. Two serving
    users share it: the prefix-cache tail prefill (argmax of the last
    offset seeds decode) and the speculative-decode verify dispatch (all
    offsets decide acceptance host-side). Requires ``supports_slots``;
    the trace key is (batch bucket, W, kv_bucket), so distinct window
    widths stay within the bucketed-compilation budget
    (``RuntimeKernels.max_traces``)."""
    mod = get_module(cfg)
    return mod.decode_window(params, tokens, cache, cfg, ctx, pages=pages,
                             pos=pos, kv_bucket=kv_bucket)


# --------------------------------------------------------------- metadata

def param_count(cfg: ArchConfig) -> int:
    import math
    tree = get_module(cfg).abstract_params(cfg)
    return sum(math.prod(l.shape) if l.shape else 1
               for l in jax.tree.leaves(tree))


def active_param_count(cfg: ArchConfig) -> int:
    """Active params per token (MoE: only top-k routed experts count)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    mo = cfg.moe
    n_moe_layers = cfg.n_layers - mo.first_k_dense
    from repro.models.layers import mlp_up_width
    per_expert = (cfg.d_model * mlp_up_width(mo.d_ff_expert, cfg.mlp)
                  + mo.d_ff_expert * cfg.d_model)
    inactive = n_moe_layers * (mo.n_routed - mo.top_k) * per_expert
    return total - inactive
