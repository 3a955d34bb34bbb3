"""Continuous-batching decode runtime: slot slab + bucketed compilation.

One ``DecodeRuntime`` per serving replica replaces the chunked
prefill-then-Python-decode path:

- **Paged KV slab** (default): KV lives in a shared pool of fixed-size
  physical pages (``model_api.init_paged_cache``); each slot owns a row
  of the host-side page table. Admission allocates exactly the pages a
  request's lifetime needs (``RuntimeConfig.page_footprint``) and
  ``pump`` frees them at retirement, so HBM per request tracks its
  actual length and the decode dispatch reads only the smallest
  ``kv_ladder`` bucket covering the deepest live row — an 8-token
  request no longer pays a 128-token request's attention cost.
  ``paged=False`` keeps the PR-2 dense slab: ``max_batch`` slots x
  ``capacity`` entries (``model_api.init_slab_cache``). Either way,
  nothing is ever re-allocated or grown per chunk.
- **Bucketed compilation**: prompts pad to power-of-two length buckets and
  admissions to power-of-two batch buckets, so the number of distinct jit
  traces is O(#length-buckets x #batch-buckets) + 1 fused decode trace,
  independent of the observed request mix. ``RuntimeKernels.trace_counts``
  exposes the actual trace tally for regression tests.
- **Fused decode**: ``decode_block`` greedy steps run as one
  ``jax.lax.scan`` dispatch with the slab donated (``model_api.fused_decode``)
  instead of one Python-loop dispatch per token.
- **Continuous batching**: after every block the host harvests finished
  slots, frees them, and admits pending requests immediately — a short
  request no longer rides along for its chunk-mates' ``max_new``.

Kernels (the jitted closures) are shared across replicas and cached per
mesh topology by ``ElasticServing.runtime_kernels``; the slab itself is
per-replica state. The slot table round-trips through the drain ->
checkpoint -> reschedule path as plain numpy arrays (``state()`` /
``restore()``), so in-flight requests survive a node eviction.

Request content store: each request's prompt tokens are materialized
once — deterministically from (rid, length bucket), independent of its
admission chunk-mates — kept in ``DecodeRuntime.content``, and carried
through ``state()``/``restore()``. A restored rid therefore replays its
*exact* prompt tokens on the successor replica, so greedy output across a
drain is token-identical to an undisturbed run.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.tracing import NULL_REGION
from repro.data.pipeline import Request
from repro.kernels.paged_decode_attention import pages_per_block
from repro.models import model_api as MA


def requests_from_state(state) -> List[Request]:
    """Decode a checkpointed slot table back into Request objects."""
    rids = np.asarray(state.get("inflight_rid", ()))
    if rids.size == 0:
        return []
    arrival = np.asarray(state["inflight_arrival"])
    plen = np.asarray(state["inflight_plen"])
    rem = np.asarray(state["inflight_remaining"])
    # prefix identity ships too (absent in pre-prefix-cache checkpoints):
    # a restored rid whose content row is missing re-mints its shared
    # prefix bit-identically, so the successor re-interns and rebuilds
    # page sharing instead of forking private copies
    grp = np.asarray(state.get("inflight_group", np.zeros(rids.size)))
    pfx = np.asarray(state.get("inflight_pfxlen", np.zeros(rids.size)))
    # QoS columns (absent in pre-overload checkpoints): the deadline and
    # tier survive the drain -> restore round trip, so a restored request
    # is still sheddable/protected exactly like a never-moved one
    ddl = np.asarray(state.get("inflight_deadline", np.zeros(rids.size)))
    pri = np.asarray(state.get("inflight_priority",
                               np.full(rids.size, 10)))
    # trace context (absent in pre-observability checkpoints): the span
    # chain keeps its identity across fault incarnations
    trc = np.asarray(state.get("inflight_trace", np.zeros(rids.size)))
    return [Request(int(rids[i]), float(arrival[i]), int(plen[i]),
                    int(rem[i]), prefix_group=int(grp[i]),
                    prefix_len=int(pfx[i]), deadline=float(ddl[i]),
                    priority=int(pri[i]), trace_id=int(trc[i]))
            for i in range(rids.size)]


@dataclass(frozen=True)
class RuntimeConfig:
    """Static shape policy — one kernels cache entry per distinct value.

    ``paged=True`` stores KV in a shared pool of ``page_size``-entry
    physical pages instead of one full-capacity row per slot: admission
    allocates each request ceil((prompt_bucket + max_new + 1) /
    page_size) pages, retirement frees them, and decode reads only the
    smallest ``kv_ladder`` bucket covering the deepest live row — HBM
    and attention cost track actual request lengths, so ``max_batch``
    can grow for short-request mixes under the same pool
    (``pool_pages``; 0 sizes the pool so every slot can hold a
    full-capacity request, i.e. no admission ever blocks on pages).
    It pays when capacity is provisioned well beyond the typical live
    depth (long-context posture, or the TPU Pallas per-row-exit path);
    with a tightly-sized slab the dense layout's single fused attention
    is faster on CPU — see ``bench_paged_decode`` for the crossover.

    The dense slab keeps its own length-proportionality lever:
    ``block_skip`` streams decode KV in blocks and the host engages it
    per dispatch whenever the deepest live row leaves at least half the
    capacity dead (0 disables — the PR-2 plain full-width attention)."""
    max_batch: int = 8            # slots in the slab
    min_prompt_bucket: int = 8
    max_prompt_bucket: int = 64
    max_new_cap: int = 64         # capacity headroom for generation
    decode_block: int = 16        # max fused steps per scan dispatch
    admit_tail: int = 4           # decode steps fused into each admission
    paged: bool = False           # paged KV pool vs dense per-slot slab
    page_size: int = 16           # KV entries per physical page
    pool_pages: int = 0           # pool size; 0 -> max_batch * pages_per_slot
    # dense-slab jnp decode: KV block size for runtime block skipping
    # (engaged per dispatch while live depth <= capacity/2); 0 restores
    # the PR-2 plain full-capacity attention everywhere
    block_skip: int = 32
    # prefix-sharing copy-on-write (paged only): admission interns each
    # prompt's page-aligned prefix; a later identical prefix splices the
    # existing pages (refcount++) instead of re-running prefill, and the
    # first write into a shared page copies it first (CoW)
    prefix_cache: bool = False
    # multi-token speculative decode (paged only): an n-gram drafter
    # proposes k tokens per row and one k+1-wide dispatch verifies them
    # (greedy accept-prefix — token-identical to one-at-a-time). 0 = off.
    spec_decode: int = 0
    # bounded pending queue (0 = unbounded): ``submit`` admits up to the
    # cap and returns the overflow so the engine can apply backpressure
    # (reject-with-retry-after) instead of letting the queue grow forever
    pending_cap: int = 0

    @property
    def capacity(self) -> int:
        # every admitted request fits without ring-wrapping; speculative
        # verify writes up to spec_decode draft positions past the last
        # accepted token, so its headroom joins the footprint
        return self.max_prompt_bucket + self.max_new_cap + 1 + self.spec_decode

    @property
    def pages_per_slot(self) -> int:
        return -(-self.capacity // self.page_size)

    @property
    def padded_capacity(self) -> int:
        return self.pages_per_slot * self.page_size

    @property
    def n_pool_pages(self) -> int:
        return self.pool_pages or self.max_batch * self.pages_per_slot

    @property
    def prompt_buckets(self) -> Tuple[int, ...]:
        return MA.bucket_ladder(self.min_prompt_bucket, self.max_prompt_bucket)

    @property
    def batch_buckets(self) -> Tuple[int, ...]:
        return MA.bucket_ladder(1, self.max_batch)

    @property
    def block_ladder(self) -> Tuple[int, ...]:
        # fused-step buckets: the host picks the smallest block covering the
        # longest live request, so tail ticks don't over-run 16 steps deep
        return MA.bucket_ladder(min(4, self.decode_block), self.decode_block)

    @property
    def kv_ladder(self) -> Tuple[int, ...]:
        # logical KV-read buckets for paged decode: every page multiple,
        # not powers of two — a row at depth 33 reads 48 entries, not 64.
        # The ladder is page-granular because reads gather whole pages;
        # its length (= pages_per_slot) is the kv factor in max_traces.
        return tuple(self.page_size * (p + 1)
                     for p in range(self.pages_per_slot))

    def page_footprint(self, plen_bucket: int, max_new: int) -> int:
        """Physical pages a request owns for its whole life: prompt bucket
        + generation + the frozen-row write slot (mirrors capacity's +1)
        + speculative-draft overshoot when spec_decode is on."""
        return -(-(plen_bucket + max_new + 1 + self.spec_decode)
                 // self.page_size)

    def cow_reserve(self, plen_bucket: int) -> int:
        """Extra pages granted at admission for copy-on-write headroom.
        Only a prompt page can ever be shared, and a row's writes overlap
        the prompt region only in the page containing ``plen_bucket`` when
        that bucket is not page-aligned — so at most one CoW per row, and
        pre-granting its target page means CoW never allocates from a
        possibly-empty pool (no deadlock against retirement)."""
        return 1 if (self.prefix_cache and plen_bucket % self.page_size) else 0

    def fits(self, req: Request) -> bool:
        if req.prompt_len > self.max_prompt_bucket:
            return False
        plen = MA.pow2_bucket(req.prompt_len, self.min_prompt_bucket,
                              self.max_prompt_bucket)
        if plen + req.max_new + 1 + self.spec_decode > self.capacity:
            return False
        return (not self.paged
                or self.page_footprint(plen, req.max_new)
                + self.cow_reserve(plen) <= self.n_pool_pages)


class PageAllocator:
    """Reference-counted free list over the physical KV page pool (unit
    granularity — a "fragment" is just a reusable page, so mid-stream
    retirement never strands capacity). Page 0 is reserved as the null
    page: pad rows, retired slots and frozen rows write there; nothing
    reads it. ``share`` lets several slots reference the same immutable
    prefix page (prefix cache); ``free`` decrements and only returns a
    page to the free list when its last reference drops.

    Invariants (asserted by tests/test_paged_runtime.py and
    tests/test_prefix_cache.py):
      - page 0 is never handed out;
      - used + free == pool size at every step (a shared page is one
        *physical* page, counted used once no matter how many holders);
      - no write access to refcount>1 pages (the runtime CoWs first —
        the PR-4 "one owner" rule generalized to "one writer");
      - ``alloc`` is all-or-nothing (no partial grants to unwind).
    """

    def __init__(self, pool_pages: int):
        self.pool_pages = pool_pages
        # LIFO: freshly freed pages are reused first (warm in cache)
        self._free = list(range(pool_pages, 0, -1))
        # refcount[p]: holders of physical page p (0 = on the free list)
        self.refcount = np.zeros(pool_pages + 1, np.int32)

    @property
    def n_pages(self) -> int:          # physical pool incl. the null page
        return self.pool_pages + 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.pool_pages - len(self._free)

    @property
    def shared_pages(self) -> int:
        """Physical pages currently referenced by more than one holder."""
        return int(np.sum(self.refcount > 1))

    def alloc(self, n: int) -> Optional[List[int]]:
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        self.refcount[out] = 1
        return out

    def share(self, pages) -> None:
        """Add one reference to each page (prefix splice). Pages must be
        live — sharing a free page would resurrect it under two owners."""
        for p in pages:
            assert self.refcount[p] > 0, f"share of free page {p}"
            self.refcount[p] += 1

    def free(self, pages) -> List[int]:
        """Drop one reference per page; pages whose count hits zero return
        to the free list. Returns the pages actually released (so the
        runtime can evict stale prefix-cache entries pointing at them)."""
        released = []
        for p in pages:
            assert self.refcount[p] > 0, f"double free of page {p}"
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self._free.append(p)
                released.append(p)
        return released


class RuntimeKernels:
    """Jitted admission + fused-decode functions with a trace-count guard.

    The python bodies below execute only while jax traces them, so the
    ``trace_counts`` increments tally *compilations*, not calls — the
    bucketing contract ("O(#buckets) traces under any request mix") is a
    plain integer assertion away.
    """

    def __init__(self, cfg: ArchConfig, rcfg: RuntimeConfig, ctx=None):
        if not MA.supports_slots(cfg):
            raise ValueError(f"family {cfg.family!r} has no slot-slab decode")
        if (rcfg.prefix_cache or rcfg.spec_decode) and not rcfg.paged:
            raise ValueError("prefix_cache / spec_decode require the paged "
                             "KV slab (paged=True)")
        if rcfg.spec_decode and rcfg.admit_tail:
            raise ValueError("spec_decode needs admit_tail=0 (acceptance is "
                             "decided host-side between dispatches, so the "
                             "device-resident fused tail would desync)")
        self.cfg, self.rcfg, self.ctx = cfg, rcfg, ctx
        self.trace_counts = {"admit": 0, "decode": 0, "splice": 0,
                             "window": 0, "cow": 0}
        self._admit = {}                 # (batch_bucket, len_bucket) -> fn
        self._decode = {}                # fused steps -> fn
        self._splice = {}                # batch_bucket -> fn
        self._window = {}                # (bb, W, kvb, P, stamp) -> fn
        self._cow = {}                   # pair-count bucket -> fn

    @property
    def max_traces(self) -> int:
        """Bucketing contract: traces stay O(#buckets) under any request
        mix. Paged decode adds the kv-read-bucket dimension (which logical
        prefix of the page table a dispatch visits), so the bound picks up
        a ``kv_ladder`` factor — still shape-policy-static. The prefix
        cache adds splice stamps, tail-prefill windows (one per (batch,
        prompt bucket, shared-page count)) and CoW copy batches;
        speculative decode adds the k+1-wide verify windows."""
        n_bb = len(self.rcfg.batch_buckets)
        n_admit = n_bb * len(self.rcfg.prompt_buckets)
        n_decode = len(self.rcfg.block_ladder)
        extra = 0
        if self.rcfg.paged:
            n_kv = len(self.rcfg.kv_ladder)
            # admissions with a fused tail also carry a kv bucket
            if self.rcfg.admit_tail:
                n_admit *= n_kv
            n_decode *= n_kv
            if self.rcfg.prefix_cache:
                # tail-less admit variants (waves containing cache hits)
                extra += n_bb * len(self.rcfg.prompt_buckets)
                extra += n_bb                        # full-hit splices
                extra += (n_bb * len(self.rcfg.prompt_buckets)
                          * self.rcfg.pages_per_slot)  # tail windows
                extra += n_bb                        # CoW copy batches
            if self.rcfg.spec_decode:
                extra += n_bb * n_kv                 # verify windows
        elif self.rcfg.block_skip:
            n_decode *= 2          # plain + block-skip variants per steps
        return n_admit + n_decode + extra

    def admit_fn(self, bb: int, lb: int, kvb: int = 0):
        key = (bb, lb, kvb)
        if key in self._admit:
            return self._admit[key]
        cfg, ctx = self.cfg, self.ctx
        mod = MA.get_module(cfg)
        rcfg = self.rcfg
        tail = rcfg.admit_tail

        def admit(params, tokens, cache, tok, active, remaining,
                  slot_idx, max_new, pages=None, prompt_pages=None):
            self.trace_counts["admit"] += 1
            logits, pcache = mod.prefill(params, tokens, cfg, ctx)
            if rcfg.paged:
                cache = MA.scatter_prefill_paged(
                    cfg, cache, pcache, slot_idx, tokens.shape[1],
                    prompt_pages, rcfg.page_size)
            else:
                cache = MA.scatter_prefill(cfg, cache, pcache, slot_idx,
                                           tokens.shape[1])
            first = jnp.argmax(logits, -1).astype(jnp.int32)
            tok = tok.at[slot_idx].set(first[:, None])
            # pad rows (batch bucket > group size) target the overflow row
            # with max_new = 0: they go inert after one masked step
            active = active.at[slot_idx].set(max_new > 0)
            remaining = remaining.at[slot_idx].set(max_new)
            # paged admissions built with kvb=0 are explicitly tail-less
            # (prefix-cache waves containing hits: spliced rows must not
            # be advanced by a fused ride they were never stamped onto)
            if tail and (kvb or not rcfg.paged):
                # fused decode tail: admission and the first few steps of
                # the whole slab ride one dispatch (half the sync points)
                # tail steps run plain on the dense slab (a freshly
                # admitted bucket usually fills a good share of capacity;
                # skipping is the decode blocks' per-dispatch decision)
                tok, cache, active, remaining, _, _ = MA.fused_decode(
                    params, tok, cache, active, remaining, cfg, ctx,
                    steps=tail, pages=pages,
                    kv_bucket=kvb if rcfg.paged else None,
                    block_skip=None if rcfg.paged else 0)
            return cache, tok, active, remaining, first

        fn = jax.jit(admit, donate_argnums=(2, 3, 4, 5))
        self._admit[key] = fn
        return fn

    def decode_fn(self, steps: int, kvb: int = 0, skip: bool = False):
        key = (steps, kvb, skip)
        if key in self._decode:
            return self._decode[key]
        cfg, ctx = self.cfg, self.ctx
        rcfg = self.rcfg

        def block(params, tok, cache, active, remaining, pages=None):
            self.trace_counts["decode"] += 1
            return MA.fused_decode(params, tok, cache, active, remaining,
                                   cfg, ctx, steps=steps, pages=pages,
                                   kv_bucket=kvb if rcfg.paged else None,
                                   block_skip=(None if rcfg.paged else
                                               (rcfg.block_skip if skip
                                                else 0)))

        fn = jax.jit(block, donate_argnums=(1, 2, 3, 4))
        self._decode[key] = fn
        return fn

    def splice_fn(self, bb: int):
        """Full prefix hit: no model evaluation at all — stamp the spliced
        rows' device state (first token from the interned entry, position
        = prompt bucket) and the admission is done. The page-table write
        itself is host-side; this is the only device work a hit costs."""
        if bb in self._splice:
            return self._splice[bb]

        def splice(cache, tok, active, remaining, idx, first, max_new, pos):
            self.trace_counts["splice"] += 1
            cache = dict(cache)
            cache["pos"] = cache["pos"].at[idx].set(pos)
            tok = tok.at[idx].set(first[:, None])
            active = active.at[idx].set(max_new > 0)
            remaining = remaining.at[idx].set(max_new)
            return cache, tok, active, remaining

        fn = jax.jit(splice, donate_argnums=(0, 1, 2, 3))
        self._splice[bb] = fn
        return fn

    def window_fn(self, bb: int, W: int, kvb: int, P: int, stamp: bool):
        """W-token decode window over a (bb,)-row subset of the paged slab:
        writes KV for all W tokens at positions pos..pos+W-1 and returns
        the greedy argmax at every offset. Two users share the trace
        family: the tail prefill of a partial prefix hit (``stamp=True``
        also stamps tok/active/remaining/pos for the admitted rows) and
        the speculative-decode verify step (``stamp=False`` — the host
        decides acceptance before device state may advance)."""
        key = (bb, W, kvb, P, stamp)
        if key in self._window:
            return self._window[key]
        cfg, ctx = self.cfg, self.ctx

        def window(params, tokens, cache, tok, active, remaining, pos,
                   pages_sub, idx, max_new):
            self.trace_counts["window"] += 1
            logits, cache = MA.decode_window(params, tokens, cache, cfg,
                                             ctx, pages=pages_sub, pos=pos,
                                             kv_bucket=kvb)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)      # (bb, W)
            if stamp:
                cache = dict(cache)
                cache["pos"] = cache["pos"].at[idx].set(pos + W)
                tok = tok.at[idx].set(toks[:, -1:])
                active = active.at[idx].set(max_new > 0)
                remaining = remaining.at[idx].set(max_new)
            return cache, tok, active, remaining, toks

        fn = jax.jit(window, donate_argnums=(2, 3, 4, 5))
        self._window[key] = fn
        return fn

    def cow_fn(self, n: int):
        """Copy-on-write transfer: duplicate ``n`` physical pages inside
        the pool (src -> dst, every layer/part) in one dispatch, before a
        write dispatch would touch a refcount>1 page. Pad pairs are
        (0, 0): the null page copied onto itself."""
        if n in self._cow:
            return self._cow[n]

        def cow(cache, src, dst):
            self.trace_counts["cow"] += 1
            new = dict(cache)
            for part in ("dense", "moe"):
                if part not in cache:
                    continue
                d = dict(cache[part])
                for nm in ("k", "v"):
                    buf = d[nm]                    # (L, n_pages, kvh, ps, dh)
                    d[nm] = buf.at[:, dst].set(buf[:, src])
                new[part] = d
            return new

        fn = jax.jit(cow, donate_argnums=(0,))
        self._cow[n] = fn
        return fn

    def put(self, tree, axes=None):
        """Commit arrays to the serving mesh: replicated, or split by the
        logical ``axes`` tree (the paged pool over KV heads). Mixing
        mesh-committed params with uncommitted slab buffers makes every
        dispatch re-shard its inputs (~15x per-call overhead on CPU), so
        all runtime state goes through here."""
        if self.ctx is None or self.ctx.mesh is None:
            return jax.tree.map(jnp.asarray, tree)
        if axes is None:
            axes = jax.tree.map(lambda _: jax.sharding.PartitionSpec(), tree)
        return jax.tree.map(
            lambda x, ax: jax.device_put(x, self.ctx.sharding(ax, x.shape)),
            tree, axes)


@dataclass
class _Slot:
    req: Optional[Request] = None
    remaining: int = 0
    lb: int = 0                       # prompt-length bucket at admission
    pages: Tuple[int, ...] = ()       # physical pages referenced (paged mode)
    # pre-granted CoW target (prefix cache, unaligned prompt bucket):
    # consumed by the row's single possible copy-on-write, freed at
    # retirement if never used
    reserve: Optional[int] = None
    # speculative decode host mirrors: emitted token history (drafter
    # input), the last emitted-but-not-yet-written token, and the full
    # prompt's content key into the shared paved-stream table
    history: Optional[list] = None
    last_tok: int = 0
    skey: Optional[bytes] = None

    @property
    def busy(self) -> bool:
        return self.req is not None

    @property
    def pos(self) -> int:
        """Current cache depth (host mirror of the device pos vector)."""
        return self.lb + (self.req.max_new - self.remaining)


@dataclass
class Finished:
    req: Request
    tokens: int                       # generated this runtime (<= req.max_new)


@dataclass
class DecodeRuntime:
    """Per-replica serving state: the slab + a host-side slot table."""
    kernels: RuntimeKernels
    params: object
    gen: int = 0                      # ElasticServing build generation
    pending: List[Request] = field(default_factory=list)
    slots: List[_Slot] = field(default_factory=list)
    # request content store: rid -> prompt tokens (length-bucket shaped);
    # checkpointed with the slot table so restored rids replay exactly
    content: Dict[int, np.ndarray] = field(default_factory=dict)
    steps_dispatched: int = 0         # fused blocks run (for perf telemetry)
    # pressure window: busy-slot / held-page peaks since the last
    # ``reset_pressure`` — ``pump()`` runs to quiescence, so end-of-tick
    # instantaneous readings would always be zero; the peak is what the
    # slab actually had to absorb this tick
    peak_slots: int = 0
    peak_pages: int = 0
    record_tokens: bool = False       # keep per-request token ids (tests)
    token_log: Dict[int, list] = field(default_factory=dict)
    # ring cap per rid on the greedy log (0 = unbounded): long soaks keep
    # the newest ``token_log_cap`` ids; ``token_log_dropped[rid]`` counts
    # the trimmed head — the explicit truncation marker that lets audits
    # align a capped log against an oracle's tail instead of its prefix
    token_log_cap: int = 0
    token_log_dropped: Dict[int, int] = field(default_factory=dict)
    # engine degrade knob: False routes decode through the plain block
    # path even when rcfg.spec_decode is configured (brownout levels >= 1
    # shed the speculative-decode luxury before shedding any request)
    spec_enabled: bool = True
    # prefix-cache telemetry (cumulative since construction)
    prefix_lookups: int = 0
    prefix_hits: int = 0
    cow_events: int = 0
    # speculative-decode telemetry
    spec_rounds: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    spec_emitted: int = 0
    # observability plane (all optional; None = zero-cost disabled path).
    # ``name`` is the replica/pod identity stamped on spans; ``sim_now``
    # mirrors the engine clock so runtime-emitted spans carry sim-time.
    # The tracer's regions (``_region``) time the host work of a step on
    # the host clock: admit_plan, prefill, first_token, decode,
    # decode_read, harvest, pages_commit.
    name: str = ""
    tracer: object = None
    metrics: object = None            # per-pod Registry (TTFT histogram)
    sim_now: float = 0.0

    def __post_init__(self):
        rcfg = self.kernels.rcfg
        if self.record_tokens and rcfg.admit_tail:
            raise ValueError("record_tokens needs admit_tail=0 (tail-step "
                             "token ids never leave the admission dispatch)")
        self.slots = [_Slot() for _ in range(rcfg.max_batch)]
        # one extra overflow row: admissions pad their batch up to a
        # power-of-two bucket and aim the pad rows here, so a group of 7
        # costs one (8, L) prefill instead of three (4/2/1, L) dispatches
        rows = rcfg.max_batch + 1
        if rcfg.paged:
            self.alloc = PageAllocator(rcfg.n_pool_pages)
            # host-owned page table, shipped with every dispatch: row ->
            # physical pages (0 = null). Freed rows are re-pointed at the
            # null page *before* their pages can be re-granted, so a
            # frozen row's idempotent KV write can never corrupt a
            # successor request's page.
            self.page_table = np.zeros((rows, rcfg.pages_per_slot), np.int32)
            self.pages_hwm = 0                  # pool high-water (telemetry)
            self._pages_dev = None              # mesh-committed copy
            self._pages_dirty = True
            self.cache = self.kernels.put(
                MA.init_paged_cache(self.kernels.cfg, rows,
                                    self.alloc.n_pages, rcfg.page_size),
                MA.paged_cache_axes(self.kernels.cfg))
        else:
            self.cache = self.kernels.put(MA.init_slab_cache(
                self.kernels.cfg, rows, rcfg.capacity))
        self.tok = self.kernels.put(jnp.zeros((rows, 1), jnp.int32))
        self.active = self.kernels.put(jnp.zeros((rows,), bool))
        self.remaining = self.kernels.put(jnp.zeros((rows,), jnp.int32))
        # prefix intern table: ("p", j, bytes) -> j page-aligned prefix
        # pages; ("f", lb, bytes) -> full prompt pages + first greedy
        # token. Entries hold no reference of their own — they stay valid
        # exactly while some slot holds the pages (refcount >= 1), and
        # are evicted the moment a release returns a listed page to the
        # pool (before any re-grant could repurpose it).
        self._intern: Dict[tuple, dict] = {}
        # paved-stream table (speculative decode): full-prompt bytes ->
        # greedy tokens some row already emitted for that exact prompt.
        # Greedy decode is deterministic in the prompt, so a later
        # identical request's tokens are known ahead of verification —
        # the drafter reads them and acceptance is ~100% (replay /
        # duplicate traffic); unseen prompts fall back to the n-gram
        # drafter. Purely an accelerator: bounded, never checkpointed.
        self._stream: Dict[bytes, list] = {}

    @property
    def _paged(self) -> bool:
        return self.kernels.rcfg.paged

    @property
    def pages_in_use(self) -> int:
        return self.alloc.used_pages if self._paged else 0

    @property
    def shared_pages(self) -> int:
        """Physical pages currently referenced by >1 slot (the
        ``ersap_shared_pages`` gauge)."""
        return self.alloc.shared_pages if self._paged else 0

    @property
    def spec_accept_rate(self) -> float:
        """Fraction of drafted tokens accepted by verification."""
        return self.spec_accepted / self.spec_drafted \
            if self.spec_drafted else 0.0

    @property
    def slots_in_use(self) -> int:
        """Busy slab slots (the dense-path pressure gauge,
        ``ersap_slab_slots_used``)."""
        return sum(s.busy for s in self.slots)

    @property
    def occupancy(self) -> float:
        """Memory-pressure fraction in [0, 1] — the HPA / twin signal:
        page-pool share when paged (HBM actually held), busy-slot share
        on the dense slab (whose HBM is fixed; slots are what run out).
        Peak over the current pressure window (see ``reset_pressure``)."""
        if self._paged:
            return max(self.pages_in_use, self.peak_pages) / \
                max(self.alloc.pool_pages, 1)
        return max(self.slots_in_use, self.peak_slots) / \
            max(len(self.slots), 1)

    def reset_pressure(self) -> None:
        """Start a new pressure-measurement window (one engine tick)."""
        self.peak_slots = self.slots_in_use
        self.peak_pages = self.pages_in_use

    def _region(self, name: str, **attrs):
        """A region of the tracer's ring on the host clock
        (``Tracer.region``), or a no-op context without a tracer. Enters
        to the span, or to ``None``."""
        if self.tracer is None:
            return NULL_REGION
        return self.tracer.region(name, self.sim_now, replica=self.name,
                                  **attrs)

    def _device_pages(self):
        """Mesh-committed page table, refreshed only when the host table
        mutated (admission/retirement) — uncommitted per-dispatch inputs
        would re-shard the whole argument list (see ``RuntimeKernels.put``)."""
        if self._pages_dirty:
            with self._region("pages_commit"):
                self._pages_dev = self.kernels.put(
                    jnp.asarray(self.page_table))
            self._pages_dirty = False
        return self._pages_dev

    def _kv_bucket(self, steps: int, incoming=()) -> int:
        """Smallest kv-read bucket covering every live row's cache depth at
        the end of a ``steps``-deep fused block (busy slots advance by at
        most min(steps, remaining); ``incoming`` rows are (lb, max_new)
        pairs about to be admitted at depth lb)."""
        need = 1
        for s in self.slots:
            if s.busy:
                need = max(need, s.pos + min(steps, s.remaining))
        for lb, max_new in incoming:
            need = max(need, lb + min(steps, max_new))
        ladder = self.kernels.rcfg.kv_ladder
        return next((b for b in ladder if b >= need), ladder[-1])

    def _kv_blocks(self, steps: int, kvb: int) -> dict:
        """The paged kernel's grid in one layer call at a block's last
        step: ``kv_blocks`` the grid steps it walks (rows x ceil(kvb /
        block)), ``live_kv_blocks`` the blocks below a busy row's depth
        (ceil(depth / block) a busy row). A block is the kernel's own
        ``pages_per_block`` pages, from the pool shard's page bytes."""
        ps = self.kernels.rcfg.page_size
        kv = next(self.cache[p]["k"] for p in ("dense", "moe")
                  if p in self.cache)
        shard = kv.sharding.shard_shape(kv.shape)  # (L, n, Hkv, ps, dh)
        page_bytes = int(np.prod(shard[2:])) * kv.dtype.itemsize
        bk = ps * pages_per_block(page_bytes, kvb // ps)
        live = sum(-(-(s.pos + min(steps, s.remaining)) // bk)
                   for s in self.slots if s.busy)
        return {"kv_blocks": len(self.page_table) * -(-kvb // bk),
                "live_kv_blocks": live}

    # -------------------------------------------------------------- intake
    def submit(self, requests: List[Request],
               force: bool = False) -> List[Request]:
        """Enqueue requests; returns the overflow rejected by the bounded
        pending queue (empty when ``pending_cap`` is 0 or everything
        fits). ``force=True`` bypasses the cap — checkpoint-restored and
        drain-carried work was already admitted once and must never be
        bounced back into the arrival stream."""
        cap = self.kernels.rcfg.pending_cap
        if force or cap <= 0:
            self.pending.extend(requests)
            return []
        room = max(cap - len(self.pending), 0)
        self.pending.extend(requests[:room])
        return list(requests[room:])

    def fits(self, req: Request) -> bool:
        return self.kernels.rcfg.fits(req)

    def _log_tokens(self, rid: int, toks: list) -> None:
        """Append to the per-rid greedy log, trimming the oldest entries
        past ``token_log_cap`` and counting the drop."""
        log = self.token_log.setdefault(rid, [])
        log.extend(toks)
        cap = self.token_log_cap
        if cap and len(log) > cap:
            drop = len(log) - cap
            del log[:drop]
            self.token_log_dropped[rid] = \
                self.token_log_dropped.get(rid, 0) + drop

    @property
    def inflight(self) -> int:
        return sum(s.busy for s in self.slots) + len(self.pending)

    # ---------------------------------------------------------- admission
    def _free_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if not s.busy]

    def _admit_some(self) -> List[Finished]:
        """Admit pending requests into free slots: group by prompt-length
        bucket (largest group first), one padded prefill dispatch per
        group (with a fused decode tail — see ``RuntimeKernels.admit_fn``).
        Hysteresis: while decode is mid-stream, wait until a couple of
        slots are free rather than paying one prefill dispatch per freed
        slot (admission is still within one decode block of arrival)."""
        if not self.pending:
            return []
        rcfg = self.kernels.rcfg
        free = self._free_slots()
        busy = rcfg.max_batch - len(free)
        if busy and len(free) < min(len(self.pending),
                                    max(2, rcfg.max_batch // 2)):
            return []
        done: List[Finished] = []
        while free and self.pending:
            with self._region("admit_plan", pending=len(self.pending),
                              free=len(free)):
                planned = self._plan_wave(len(free))
            if planned is None:
                break
            group, lb, grants = planned
            take, free = free[:len(group)], free[len(group):]
            done.extend(self._admit_batch(group, take, lb, grants))
        return done

    def _plan_wave(self, n_free: int):
        """The next admission wave, taken off ``pending``: the largest
        (length bucket, depth bucket) group, longest-first, cut to
        ``n_free`` slots and, when paged, to what the pool grants now.
        Returns (requests, lb, grants), or None when nothing is granted."""
        rcfg = self.kernels.rcfg
        groups: Dict[tuple, List[Request]] = {}
        for r in self.pending:
            lb = MA.pow2_bucket(r.prompt_len, rcfg.min_prompt_bucket,
                                rcfg.max_prompt_bucket)
            # depth-segregated admission: co-schedule rows whose
            # generation depth shares a pow2 bucket, so one deep row
            # doesn't pin the block/kv ladder (and the CoW working
            # set) for a wave of short batch-mates
            db = MA.pow2_bucket(max(r.max_new, 1), 1, rcfg.max_new_cap)
            groups.setdefault((lb, db), []).append(r)
        (lb, _), group = max(groups.items(), key=lambda kv: len(kv[1]))
        # within the depth bucket, longest-first keeps fused blocks tight
        group = sorted(group, key=lambda r: -r.max_new)[:n_free]
        grants: Dict[int, dict] = {}
        if self._paged:
            # all-or-nothing page grant per request; a request the pool
            # cannot hold right now stays pending until a retirement
            # frees pages (fits() guarantees it can be held eventually)
            granted = []
            wave: Dict[tuple, dict] = {}    # same-wave leader full keys
            for r in group:
                g = self._plan_grant(r, lb, wave)
                if g is None:
                    break
                granted.append(r)
                grants[id(r)] = g
            group = granted
            if not group:
                return None
            self.pages_hwm = max(self.pages_hwm, self.alloc.used_pages)
            self.peak_pages = max(self.peak_pages, self.alloc.used_pages)
        taken = set(id(r) for r in group)
        self.pending = [r for r in self.pending if id(r) not in taken]
        return group, lb, grants

    # ---------------------------------------------------- prefix interning
    def _lookup_prefix(self, tokens: np.ndarray, lb: int,
                       wave: Optional[Dict[tuple, dict]] = None):
        """Longest known prefix of ``tokens``: the full prompt first
        (splice-only admission), then page-aligned prefixes longest-first
        (splice + short tail prefill). ``wave`` holds prefixes granted
        earlier in the same admission wave but not yet prefilled — safe
        to share because misses dispatch before tail groups and tail
        groups dispatch in ascending j (a sharer's pages are always
        written by an earlier dispatch of the same wave)."""
        ps = self.kernels.rcfg.page_size
        e = self._intern.get(("f", lb, tokens.tobytes()))
        if e is not None:
            return ("full", e)
        # partials must leave a non-empty tail: a page-aligned prompt whose
        # whole content matches a longer prompt's head (j*ps == lb) has no
        # remainder to prefill and no recorded first token — treat as miss
        for j in range((lb - 1) // ps, 0, -1):
            key = ("p", j, tokens[:j * ps].tobytes())
            e = self._intern.get(key)
            if e is None and wave is not None:
                e = wave.get(key)
            if e is not None:
                return ("tail", j, e)
        return ("miss",)

    def _register_intern(self, tokens: np.ndarray, pages, first_tok: int,
                         lb: int) -> None:
        """Publish a freshly prefilled prompt's page-aligned prefixes and
        its full key. ``setdefault`` keeps the first publisher — its pages
        are the ones later holders already share."""
        ps = self.kernels.rcfg.page_size
        for j in range(1, lb // ps + 1):
            self._intern.setdefault(("p", j, tokens[:j * ps].tobytes()),
                                    {"pages": tuple(pages[:j]),
                                     "first": None})
        n_prompt = -(-lb // ps)
        self._intern.setdefault(("f", lb, tokens.tobytes()),
                                {"pages": tuple(pages[:n_prompt]),
                                 "first": int(first_tok)})

    def _evict_intern(self, released) -> None:
        """Drop intern entries listing any just-released page — eagerly,
        before a re-grant could repurpose the page under a stale entry."""
        rel = set(released)
        dead = [k for k, e in self._intern.items()
                if rel.intersection(e["pages"])]
        for k in dead:
            del self._intern[k]

    def _plan_grant(self, r: Request, lb: int, wave: Dict[tuple, dict]):
        """Page grant + prefix classification for one admission candidate.
        Returns None when the pool cannot hold it right now (all-or-
        nothing, like PR-4). Mutates the allocator: private pages are
        alloc'd, shared prefix pages refcount++."""
        rcfg = self.kernels.rcfg
        fp = rcfg.page_footprint(lb, r.max_new)
        if not rcfg.prefix_cache:
            pgs = self.alloc.alloc(fp)
            if pgs is None:
                return None
            return {"kind": "miss", "pages": pgs, "reserve": None}
        res = rcfg.cow_reserve(lb)
        tokens = self._prompt_tokens(r, lb)
        self.prefix_lookups += 1
        n_prompt = -(-lb // rcfg.page_size)
        fkey = ("f", lb, tokens.tobytes())

        def grant(kind, shared, extra):
            pgs = self.alloc.alloc(fp - len(shared) + res)
            if pgs is None:
                return None
            self.alloc.share(shared)
            reserve = pgs.pop() if res else None
            g = {"kind": kind, "pages": list(shared) + pgs,
                 "reserve": reserve}
            g.update(extra)
            return g

        lead = wave.get(fkey)
        if lead is not None:             # same-wave duplicate: follow it
            g = grant("follow", lead["pages"][:n_prompt], {"lead": lead})
            if g is not None:
                self.prefix_hits += 1
            return g
        hit = self._lookup_prefix(tokens, lb, wave)
        if hit[0] == "full":
            g = grant("full", hit[1]["pages"], {"first": hit[1]["first"]})
            if g is not None:
                self.prefix_hits += 1
            return g
        if hit[0] == "tail":
            j = hit[1]
            g = grant("tail", hit[2]["pages"], {"j": j})
            if g is not None:
                self.prefix_hits += 1
                self._wave_publish(wave, tokens, g, lb, fkey)
            return g
        g = grant("miss", (), {})
        if g is not None:
            self._wave_publish(wave, tokens, g, lb, fkey)
        return g

    def _wave_publish(self, wave: Dict[tuple, dict], tokens: np.ndarray,
                      g: dict, lb: int, fkey: tuple) -> None:
        """Make a just-granted miss/tail visible to later candidates of
        the same wave: the full key (exact-duplicate followers splice it)
        and every page-aligned partial (shared-prefix mates share the
        leading pages and tail-prefill only their remainder). Partial
        entries slice the leader's prompt pages at plan time — the KV for
        those pages is written by the leader's own dispatch, which the
        wave's dispatch order guarantees runs first."""
        wave[fkey] = g
        ps = self.kernels.rcfg.page_size
        for j in range(1, lb // ps + 1):
            wave.setdefault(("p", j, tokens[:j * ps].tobytes()),
                            {"pages": tuple(g["pages"][:j])})

    def _prompt_tokens(self, r: Request, lb: int) -> np.ndarray:
        """Content-store lookup: a request's prompt tokens are minted once
        (deterministic in (rid, length bucket) — never in the admission
        grouping) and replayed verbatim on every later admission,
        including after a checkpoint/restore on another replica. A
        request carrying a prefix identity gets its group's common tokens
        up front (deterministic in the group alone, so sharing survives
        drain/restore and is independent of which replica mints first)."""
        tok = self.content.get(r.rid)
        if tok is None or tok.shape[0] != lb:
            rng = np.random.default_rng(hash((r.rid, lb)) % (2 ** 31))
            tok = rng.integers(0, self.kernels.cfg.vocab, lb).astype(np.int32)
            pfx = min(r.prefix_len, lb) if r.prefix_group else 0
            if pfx:
                grng = np.random.default_rng(
                    hash(("prefix", r.prefix_group)) % (2 ** 31))
                tok[:pfx] = grng.integers(0, self.kernels.cfg.vocab, pfx)
            self.content[r.rid] = tok
        return tok

    def _note_admission(self, reqs: List[Request],
                        grants: Dict[int, dict], lb: int) -> None:
        """Observability head of an admission wave, at dispatch start:
        per-rid ``admit`` spans (with the grant kind) and the TTFT
        histogram (sim-time from arrival to first token, which admission
        produces)."""
        if self.metrics is not None:
            h = self.metrics.histogram("ersap_ttft_s")
            for r in reqs:
                h.observe(max(self.sim_now - r.arrival, 0.0))
        if self.tracer is None:
            return
        for r in reqs:
            self.tracer.span("admit", self.sim_now, rid=r.rid,
                             kind=grants.get(id(r), {}).get("kind", "miss"),
                             replica=self.name, lb=lb)

    def _admit_batch(self, reqs: List[Request], slot_idx: List[int],
                     lb: int, grants: Dict[int, dict]) -> List[Finished]:
        rcfg = self.kernels.rcfg
        self._note_admission(reqs, grants, lb)
        rids = tuple(r.rid for r in reqs) if self.tracer is not None else ()
        if (self._paged and rcfg.prefix_cache
                and any(grants[id(r)]["kind"] != "miss" for r in reqs)):
            # the wave's dispatches read their first tokens as they go
            with self._region("prefill", lb=lb, rids=rids):
                return self._admit_batch_prefix(reqs, slot_idx, lb, grants)
        bb = MA.pow2_bucket(len(reqs), 1, rcfg.max_batch)
        with self._region("prefill", bb=bb, lb=lb, rids=rids):
            first = self._prefill(reqs, slot_idx, lb, grants, bb)
        if rcfg.prefix_cache or rcfg.spec_decode or self.record_tokens:
            # prefill argmaxes, (bb,): the wave's first tokens on the host
            with self._region("first_token", rids=rids):
                first = np.asarray(first)
        for j, (r, i) in enumerate(zip(reqs, slot_idx)):
            g = grants.get(id(r), {})
            s = _Slot(req=r, remaining=int(r.max_new), lb=lb,
                      pages=tuple(g.get("pages", ())),
                      reserve=g.get("reserve"))
            if rcfg.spec_decode:
                self._spec_init(s, int(first[j]))
            self.slots[i] = s
            if rcfg.prefix_cache:
                self._register_intern(self.content[r.rid], s.pages,
                                      int(first[j]), lb)
            if self.record_tokens:               # first token (prefill argmax)
                self._log_tokens(r.rid, [int(first[j])])
        self.peak_slots = max(self.peak_slots, self.slots_in_use)
        # the fused tail advanced every live row (old and new) tail steps
        return self._harvest(rcfg.admit_tail)

    def _prefill(self, reqs: List[Request], slot_idx: List[int], lb: int,
                 grants: Dict[int, dict], bb: int):
        """Publish the wave's page grants and dispatch its ``(bb, lb)``
        prefill; returns the device array of its first tokens."""
        rcfg = self.kernels.rcfg
        n_pad = bb - len(reqs)
        # synthetic workload: the prompt is per-request noise from the
        # content store; right-pad to the length bucket and the pad joins
        # the (synthetic) context. Batch pads to the bucket too — pad rows
        # land in the overflow row, so their token values are irrelevant.
        tokens = np.stack([self._prompt_tokens(r, lb) for r in reqs]
                          + [np.zeros(lb, np.int32)] * n_pad)
        max_new = np.asarray([r.max_new for r in reqs] + [0] * n_pad,
                             np.int32)
        idx = np.asarray(list(slot_idx) + [rcfg.max_batch] * n_pad, np.int32)
        if self._paged:
            # publish the grants in the page table (pad rows -> null page)
            npg_prompt = -(-lb // rcfg.page_size)
            prompt_pages = np.zeros((bb, npg_prompt), np.int32)
            for j, (r, i) in enumerate(zip(reqs, slot_idx)):
                pgs = grants[id(r)]["pages"]
                self.page_table[i] = 0
                self.page_table[i, :len(pgs)] = pgs
                prompt_pages[j] = pgs[:npg_prompt]
            self._pages_dirty = True
            if rcfg.admit_tail:
                # the fused tail writes into already-busy rows too: any
                # shared page in their write range is copied first
                self._cow_before_write(
                    [(i, s.pos + min(rcfg.admit_tail, s.remaining))
                     for i, s in enumerate(self.slots) if s.busy])
            kvb = self._kv_bucket(rcfg.admit_tail,
                                  incoming=[(lb, int(r.max_new))
                                            for r in reqs])
            fn = self.kernels.admit_fn(bb, lb,
                                       kvb if rcfg.admit_tail else 0)
            self.cache, self.tok, self.active, self.remaining, first = fn(
                self.params, tokens, self.cache, self.tok,
                self.active, self.remaining, idx, max_new,
                pages=self._device_pages(), prompt_pages=prompt_pages)
        else:
            fn = self.kernels.admit_fn(bb, lb)
            # small host inputs commit inside the dispatch; only the
            # persistent slab state must live pre-committed on the mesh
            # (see kernels.put)
            self.cache, self.tok, self.active, self.remaining, first = fn(
                self.params, tokens, self.cache, self.tok,
                self.active, self.remaining, idx, max_new)
        return first

    def _admit_batch_prefix(self, reqs: List[Request], slot_idx: List[int],
                            lb: int,
                            grants: Dict[int, dict]) -> List[Finished]:
        """Admission wave containing prefix-cache hits. Misses prefill
        first (publishing their prefixes for same-wave followers), then
        partial hits run their short tail prefill, then full hits and
        followers are spliced with a host-side page-table write plus one
        device stamp — no prefill compute at all. No fused tail: hit rows
        are stamped after the miss dispatch, so a tail would advance rows
        asymmetrically; the next decode block picks everyone up."""
        rcfg = self.kernels.rcfg
        ps = rcfg.page_size
        n_prompt = -(-lb // ps)
        kinds = {"miss": [], "tail": [], "full": [], "follow": []}
        row_of: Dict[int, int] = {}      # id(grant) -> slab row
        first_of: Dict[int, int] = {}    # slab row -> first greedy token
        for r, i in zip(reqs, slot_idx):
            g = grants[id(r)]
            kinds[g["kind"]].append((r, i, g))
            row_of[id(g)] = i
            pgs = g["pages"]
            self.page_table[i] = 0
            self.page_table[i, :len(pgs)] = pgs
        self._pages_dirty = True

        ms = kinds["miss"]
        if ms:
            bb = MA.pow2_bucket(len(ms), 1, rcfg.max_batch)
            n_pad = bb - len(ms)
            tokens = np.stack([self.content[r.rid] for r, _, _ in ms]
                              + [np.zeros(lb, np.int32)] * n_pad)
            max_new = np.asarray([r.max_new for r, _, _ in ms]
                                 + [0] * n_pad, np.int32)
            idx = np.asarray([i for _, i, _ in ms]
                             + [rcfg.max_batch] * n_pad, np.int32)
            prompt_pages = np.zeros((bb, n_prompt), np.int32)
            for j, (r, i, g) in enumerate(ms):
                prompt_pages[j] = g["pages"][:n_prompt]
            fn = self.kernels.admit_fn(bb, lb, 0)        # tail-less
            self.cache, self.tok, self.active, self.remaining, first = fn(
                self.params, tokens, self.cache, self.tok, self.active,
                self.remaining, idx, max_new, pages=self._device_pages(),
                prompt_pages=prompt_pages)
            first = np.asarray(first)
            for j, (r, i, g) in enumerate(ms):
                first_of[i] = int(first[j])
                self._register_intern(self.content[r.rid], g["pages"],
                                      int(first[j]), lb)

        # partial hits: splice the shared full pages, prefill only the
        # non-shared remainder [j*page_size, lb) at its page-aligned offset
        for jv in sorted({g["j"] for _, _, g in kinds["tail"]}):
            grp = [t for t in kinds["tail"] if t[2]["j"] == jv]
            W = lb - jv * ps
            bb = MA.pow2_bucket(len(grp), 1, rcfg.max_batch)
            n_pad = bb - len(grp)
            toks_in = np.zeros((bb, W), np.int32)
            pos = np.zeros(bb, np.int32)
            pages_sub = np.zeros((bb, n_prompt), np.int32)
            idx = np.asarray([i for _, i, _ in grp]
                             + [rcfg.max_batch] * n_pad, np.int32)
            max_new = np.asarray([r.max_new for r, _, _ in grp]
                                 + [0] * n_pad, np.int32)
            for j2, (r, i, g) in enumerate(grp):
                toks_in[j2] = self.content[r.rid][jv * ps:lb]
                pos[j2] = jv * ps
                pages_sub[j2] = g["pages"][:n_prompt]
            fn = self.kernels.window_fn(bb, W, n_prompt * ps, n_prompt,
                                        stamp=True)
            self.cache, self.tok, self.active, self.remaining, toks = fn(
                self.params, toks_in, self.cache, self.tok, self.active,
                self.remaining, pos, pages_sub, idx, max_new)
            toks = np.asarray(toks)
            for j2, (r, i, g) in enumerate(grp):
                first_of[i] = int(toks[j2, -1])
                self._register_intern(self.content[r.rid], g["pages"],
                                      int(toks[j2, -1]), lb)

        fl = kinds["full"] + kinds["follow"]
        if fl:
            for r, i, g in fl:
                first_of[i] = first_of[row_of[id(g["lead"])]] \
                    if g["kind"] == "follow" else int(g["first"])
            bb = MA.pow2_bucket(len(fl), 1, rcfg.max_batch)
            n_pad = bb - len(fl)
            idx = np.asarray([i for _, i, _ in fl]
                             + [rcfg.max_batch] * n_pad, np.int32)
            first = np.asarray([first_of[i] for _, i, _ in fl]
                               + [0] * n_pad, np.int32)
            max_new = np.asarray([r.max_new for r, _, _ in fl]
                                 + [0] * n_pad, np.int32)
            pos = np.asarray([lb] * len(fl) + [0] * n_pad, np.int32)
            fn = self.kernels.splice_fn(bb)
            self.cache, self.tok, self.active, self.remaining = fn(
                self.cache, self.tok, self.active, self.remaining, idx,
                first, max_new, pos)

        for r, i in zip(reqs, slot_idx):
            g = grants[id(r)]
            s = _Slot(req=r, remaining=int(r.max_new), lb=lb,
                      pages=tuple(g["pages"]), reserve=g["reserve"])
            if rcfg.spec_decode:
                self._spec_init(s, first_of[i])
            self.slots[i] = s
            if self.record_tokens:
                self._log_tokens(r.rid, [first_of[i]])
        self.peak_slots = max(self.peak_slots, self.slots_in_use)
        return self._harvest(0)

    # ------------------------------------------------------- copy-on-write
    def _cow_before_write(self, writes) -> None:
        """Before any dispatch that writes KV for rows holding shared
        pages: for each (row, upper) pair — upper = the deepest position
        the dispatch may write — copy every refcount>1 page in the write
        range into the row's pre-granted reserve page and swap the table
        entry. A shared page's content is immutable from the moment a
        second holder splices it, so the writer forks, never the readers.
        At most one CoW can ever fire per row (see RuntimeConfig.
        cow_reserve), hence one reserve page suffices for a slot's life."""
        rcfg = self.kernels.rcfg
        if not rcfg.prefix_cache:
            return
        ps = rcfg.page_size
        pairs = []
        for i, upper in writes:
            s = self.slots[i]
            if not s.busy or not s.pages:
                continue
            hi = min((upper - 1) // ps, len(s.pages) - 1)
            for lp in range(s.pos // ps, hi + 1):
                old = s.pages[lp]
                if self.alloc.refcount[old] <= 1:
                    continue
                new = s.reserve
                assert new is not None, "CoW without a reserve page"
                s.reserve = None
                pg = list(s.pages)
                pg[lp] = new
                s.pages = tuple(pg)
                self.page_table[i, lp] = new
                self._pages_dirty = True
                pairs.append((old, new))
                self.alloc.free([old])     # refcount>1: drops a holder only
                self.cow_events += 1
        if pairs:
            n = MA.pow2_bucket(len(pairs), 1, rcfg.max_batch)
            pairs += [(0, 0)] * (n - len(pairs))   # null page onto itself
            src = np.asarray([p[0] for p in pairs], np.int32)
            dst = np.asarray([p[1] for p in pairs], np.int32)
            self.cache = self.kernels.cow_fn(n)(self.cache, src, dst)

    # -------------------------------------------------------------- decode
    def _retire_slot(self, i: int) -> None:
        """Free slot ``i``: in paged mode its pages go back to the pool and
        its page-table row re-points at the null page, so the retired
        row's frozen KV write can never land in a re-granted page."""
        s = self.slots[i]
        if self._paged and (s.pages or s.reserve is not None):
            self.page_table[i] = 0
            self._pages_dirty = True
            held = list(s.pages)
            if s.reserve is not None:       # unused CoW reserve goes back too
                held.append(s.reserve)
            released = self.alloc.free(held)
            if released and self._intern:
                self._evict_intern(released)
        self.slots[i] = _Slot()

    def _harvest(self, steps: int) -> List[Finished]:
        with self._region("harvest") as sp:
            done = []
            for i, s in enumerate(self.slots):
                if not s.busy:
                    continue
                s.remaining -= min(steps, s.remaining)
                if s.remaining == 0:
                    done.append(Finished(s.req, s.req.max_new))
                    self._retire_slot(i)
                    # content store follows the live request set (re-
                    # mintable deterministically) — no monotonic growth
                    # across a stream
                    self.content.pop(s.req.rid, None)
            if sp is not None:
                sp.attrs["finished"] = len(done)
        return done

    def _decode_block(self) -> List[Finished]:
        """One fused decode block over every row of the table, in the
        ``decode`` region: its shape and its live row-steps (each busy row
        computes ``steps`` and delivers ``min(steps, remaining)`` of them;
        free rows and the overflow row deliver none)."""
        rcfg = self.kernels.rcfg
        if rcfg.spec_decode and self.spec_enabled:
            return self._spec_block()
        with self._region("decode") as sp:
            maxrem = max((s.remaining for s in self.slots if s.busy),
                         default=0)
            steps = next((b for b in self.kernels.rcfg.block_ladder
                          if b >= maxrem), self.kernels.rcfg.decode_block)
            kvb = 0
            if self._paged:
                self._cow_before_write(
                    [(i, s.pos + min(steps, s.remaining))
                     for i, s in enumerate(self.slots) if s.busy])
                kvb = self._kv_bucket(steps)
                fn = self.kernels.decode_fn(steps, kvb)
                kw = {"pages": self._device_pages()}
            else:
                # engage dense block skipping only when the deepest live
                # row leaves at least half the slab capacity dead this
                # block — with a well-utilized slab the single fused
                # attention wins
                depth = max((s.pos + min(steps, s.remaining)
                             for s in self.slots if s.busy), default=0)
                skip = bool(rcfg.block_skip) and 2 * depth <= rcfg.capacity
                fn = self.kernels.decode_fn(steps, skip=skip)
                kw = {}
            before = {i: s.remaining for i, s in enumerate(self.slots)
                      if s.busy}
            if sp is not None:
                sp.attrs.update(
                    steps=steps, kvb=kvb,
                    rids=tuple(self.slots[i].req.rid for i in before),
                    rows=rcfg.max_batch + 1,
                    live_row_steps=sum(min(steps, r)
                                       for r in before.values()))
                if self._paged:
                    sp.attrs.update(self._kv_blocks(steps, kvb))
            self.tok, self.cache, self.active, self.remaining, toks, _ = fn(
                self.params, self.tok, self.cache, self.active,
                self.remaining, **kw)
            self.steps_dispatched += 1
            if self.record_tokens or rcfg.spec_decode:  # syncs per block
                with self._region("decode_read"):
                    arr = np.asarray(toks)
                for i, rem in before.items():
                    s = self.slots[i]
                    emitted = [int(t) for t in arr[:min(steps, rem), i]]
                    if self.record_tokens:
                        self._log_tokens(s.req.rid, emitted)
                    if (rcfg.spec_decode and emitted
                            and s.history is not None):
                        # keep the drafter's host mirrors current while
                        # spec is browned out, so re-enabling it later
                        # verifies against the true last token instead of
                        # a stale one
                        s.history.extend(emitted)
                        s.last_tok = emitted[-1]
            return self._harvest(steps)

    # ------------------------------------------------------ spec decode
    def _spec_init(self, s: _Slot, first: int) -> None:
        """Host mirrors for a freshly admitted spec-decode row: the token
        history (drafter input), the content key into the paved-stream
        table, and the stream's first entry if this prompt is unseen."""
        s.history = self.content[s.req.rid].tolist() + [first]
        s.last_tok = first
        s.skey = self.content[s.req.rid].tobytes()
        st = self._stream.setdefault(s.skey, [])
        if not st:
            st.append(first)
            while len(self._stream) > 256:      # bound the table
                self._stream.pop(next(iter(self._stream)))

    def _draft(self, s: _Slot, k: int) -> list:
        """Two-tier drafter. Tier 1: the paved-stream table — if some row
        already emitted further along this exact prompt's greedy stream,
        its tokens ARE this row's future (greedy decode is deterministic
        in the prompt), so propose them directly. Tier 2: self-
        speculative n-gram — latest earlier occurrence of the trailing
        bigram (then unigram) in the row's own history proposes its
        continuation; loops/templates in greedy output make it land.
        Always returns exactly k tokens (bad guesses only cost
        acceptance, never correctness)."""
        hist = s.history
        eidx = len(hist) - s.lb              # tokens this row emitted
        st = self._stream.get(s.skey)
        out: list = []
        if st and len(st) > eidx:
            out = st[eidx:eidx + k]
        n = len(hist)
        if not out and n >= 2:
            for i in range(n - 3, -1, -1):
                if hist[i] == hist[-2] and hist[i + 1] == hist[-1]:
                    out = hist[i + 2:i + 2 + k]
                    break
        if not out and n >= 1:
            for i in range(n - 2, -1, -1):
                if hist[i] == hist[-1]:
                    out = hist[i + 1:i + 1 + k]
                    break
        if not out:
            out = [hist[-1] if hist else 0]
        while len(out) < k:
            out.append(out[-1])
        return out[:k]

    def _spec_block(self) -> List[Finished]:
        """One speculative round: draft k tokens per live row, verify all
        of them in a single (k+1)-wide window dispatch, accept the longest
        draft prefix matching the greedy argmaxes host-side. Exact
        greedy equivalence: position t's argmax is conditioned only on
        truly-emitted tokens once drafts 1..t-1 matched, so every emitted
        token equals what one-token-at-a-time would have produced; the
        mismatch position itself still yields one correct token (the
        argmax the drafts never influenced), so each round emits >= 1.
        Rejected drafts leave stale KV past the accepted depth — the next
        round's window rewrites those positions before any read."""
        rcfg = self.kernels.rcfg
        k = rcfg.spec_decode
        W = k + 1
        rows = [(i, s) for i, s in enumerate(self.slots) if s.busy]
        if not rows:
            return []
        if self.tracer is not None:
            self.tracer.span("decode", self.sim_now, replica=self.name,
                             steps=W, spec=True,
                             rids=tuple(s.req.rid for _, s in rows))
        self._cow_before_write([(i, s.pos + W) for i, s in rows])
        bb = MA.pow2_bucket(len(rows), 1, rcfg.max_batch)
        n_pad = bb - len(rows)
        toks_in = np.zeros((bb, W), np.int32)
        pos = np.zeros(bb, np.int32)
        for j, (i, s) in enumerate(rows):
            toks_in[j, 0] = s.last_tok
            toks_in[j, 1:] = self._draft(s, k)
            pos[j] = s.pos
        idx = np.asarray([i for i, _ in rows]
                         + [rcfg.max_batch] * n_pad, np.int32)
        need = int(pos.max()) + W
        ladder = rcfg.kv_ladder
        kvb = next((b for b in ladder if b >= need), ladder[-1])
        pages_sub = self.page_table[idx]
        fn = self.kernels.window_fn(bb, W, kvb, rcfg.pages_per_slot,
                                    stamp=False)
        self.cache, self.tok, self.active, self.remaining, toks = fn(
            self.params, toks_in, self.cache, self.tok, self.active,
            self.remaining, pos, pages_sub, idx,
            np.zeros(bb, np.int32))
        self.steps_dispatched += 1
        out = np.asarray(toks)
        done: List[Finished] = []
        self.spec_rounds += 1
        for j, (i, s) in enumerate(rows):
            g = out[j]                          # greedy argmaxes, (W,)
            m = 0
            while m < k and g[m] == toks_in[j, 1 + m]:
                m += 1
            e = min(m + 1, s.remaining)
            emitted = [int(t) for t in g[:e]]
            self.spec_drafted += k
            self.spec_accepted += m
            self.spec_emitted += e
            if self.record_tokens:
                self._log_tokens(s.req.rid, emitted)
            eidx = len(s.history) - s.lb        # emitted before this round
            st = self._stream.get(s.skey)
            if st is not None and eidx + e > len(st):
                # this row is the stream's frontier: pave for later twins
                st.extend(emitted[len(st) - eidx:])
            s.history.extend(emitted)
            s.last_tok = emitted[-1]
            s.remaining -= e
            if s.remaining == 0:
                done.append(Finished(s.req, s.req.max_new))
                self._retire_slot(i)
                self.content.pop(s.req.rid, None)
        return done

    def pump(self) -> List[Finished]:
        """Run to quiescence: admit -> fused block -> harvest -> admit ...
        Finished slots free mid-stream; arrivals join the very next block.
        Loops on pending too: when a whole admission finishes inside its
        fused tail, the slots it freed must be refilled before returning."""
        done = self._admit_some()
        while any(s.busy for s in self.slots) or self.pending:
            if any(s.busy for s in self.slots):
                done.extend(self._decode_block())
            done.extend(self._admit_some())
        return done

    def step(self) -> List[Finished]:
        """One admission + one fused block (partial progress — lets callers
        interleave checkpoints or new arrivals between blocks)."""
        done = self._admit_some()
        if not any(s.busy for s in self.slots):
            return done
        done.extend(self._decode_block())
        done.extend(self._admit_some())
        return done

    # --------------------------------------------------------- checkpoint
    def partial_tokens(self) -> int:
        """Tokens generated for still-running requests (credited into the
        checkpointed counters so finish-time credit of the remainder on
        the successor replica sums to exactly ``max_new`` per request)."""
        return sum(s.req.max_new - s.remaining for s in self.slots if s.busy)

    def state(self) -> Dict[str, np.ndarray]:
        """Slot table + pending queue as flat numpy arrays (what the drain
        controller can save through ``repro.checkpoint``). Restoration
        re-prefills — KV is derivable state; the request ledger and the
        content store (exact prompt tokens) are not, so both ship.
        Physical page ids are replica-local and deliberately absent: the
        successor's admission re-allocates from its own pool and rebuilds
        its page table, replaying identical tokens (the §4.5.4 page-table
        round-trip is logical, not physical)."""
        live = [(s.req.rid, s.req.arrival, s.req.prompt_len, s.remaining,
                 s.req.prefix_group, s.req.prefix_len,
                 s.req.deadline, s.req.priority, s.req.trace_id)
                for s in self.slots if s.busy and s.remaining > 0]
        live += [(r.rid, r.arrival, r.prompt_len, r.max_new,
                  r.prefix_group, r.prefix_len, r.deadline, r.priority,
                  r.trace_id)
                 for r in self.pending]
        arr = np.asarray(live, np.float64).reshape(-1, 9)
        rids = arr[:, 0].astype(np.int64)
        # content rows for the in-flight rids, padded to one rectangle
        toks = [self.content.get(int(rid), np.zeros(0, np.int32))
                for rid in rids]
        width = max((t.shape[0] for t in toks), default=0)
        content = np.zeros((len(toks), width), np.int32)
        for i, t in enumerate(toks):
            content[i, :t.shape[0]] = t
        return {
            "inflight_rid": rids,
            "inflight_arrival": arr[:, 1],
            "inflight_plen": arr[:, 2].astype(np.int64),
            "inflight_remaining": arr[:, 3].astype(np.int64),
            "inflight_group": arr[:, 4].astype(np.int64),
            "inflight_pfxlen": arr[:, 5].astype(np.int64),
            "inflight_deadline": arr[:, 6],
            "inflight_priority": arr[:, 7].astype(np.int64),
            "inflight_trace": arr[:, 8].astype(np.int64),
            "content_len": np.asarray([t.shape[0] for t in toks], np.int64),
            "content_tokens": content,
        }

    def ingest_content(self, state) -> None:
        """Adopt a checkpoint's content-store rows: restored rids replay
        their exact prompt tokens instead of re-randomizing."""
        rids = np.asarray(state.get("inflight_rid", ()))
        lens = np.asarray(state.get("content_len", ()))
        toks = np.asarray(state.get("content_tokens", ()))
        for i in range(min(rids.size, lens.size)):
            if lens[i] > 0:
                self.content[int(rids[i])] = \
                    toks[i, :int(lens[i])].astype(np.int32)

    def restore(self, state: Dict[str, np.ndarray]):
        """Re-enqueue checkpointed in-flight requests (counted tokens were
        already credited by the predecessor; ``max_new`` = what remains)."""
        self.ingest_content(state)
        self.pending.extend(requests_from_state(state))

    def drain(self) -> List[Request]:
        """Give back every in-flight request (runtime retirement path).
        The content store empties with it: whichever runtime re-admits a
        drained rid re-mints the identical tokens."""
        out = list(self.pending)
        self.pending = []
        for i, s in enumerate(self.slots):
            if s.busy:
                out.append(Request(s.req.rid, s.req.arrival,
                                   s.req.prompt_len, s.remaining,
                                   prefix_group=s.req.prefix_group,
                                   prefix_len=s.req.prefix_len,
                                   deadline=s.req.deadline,
                                   priority=s.req.priority,
                                   trace_id=s.req.trace_id))
                self._retire_slot(i)
        self.content.clear()
        return out
