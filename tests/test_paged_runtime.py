"""Paged KV slab (PR 4): page allocator invariants, paged-vs-dense token
identity, pool-exhaustion backpressure, fragmentation-free reuse across
mid-stream retirement, page-table checkpoint round-trip through the
§4.5.4 drain loop, trace bounds under randomized shapes, and the
equal-HBM wide-batch configuration."""
import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.configs.base import get_config
from repro.core.elastic import ElasticServing
from repro.data.pipeline import Request
from repro.streaming.runtime import (DecodeRuntime, PageAllocator,
                                     RuntimeConfig)


@pytest.fixture(scope="module")
def serving():
    cfg = get_config("qwen2-7b").reduced()
    return ElasticServing(cfg, tp=1).build(1, seed=0)


def mk_runtime(serving, rcfg, **kw):
    return DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                         gen=serving.build_gen, **kw)


def paged_cfg(**kw):
    base = dict(max_batch=4, paged=True, page_size=16)
    base.update(kw)
    return RuntimeConfig(**base)


def used_by_slots(rt):
    return sum(len(s.pages) for s in rt.slots if s.busy)


# ------------------------------------------------------------- allocator

def test_page_allocator_alloc_free_reuse():
    a = PageAllocator(6)
    assert a.n_pages == 7 and a.free_pages == 6 and a.used_pages == 0
    g1 = a.alloc(2)
    g2 = a.alloc(3)
    assert g1 is not None and g2 is not None
    assert 0 not in g1 + g2                  # null page never granted
    assert len(set(g1) | set(g2)) == 5       # no page owned twice
    assert a.used_pages == 5
    # all-or-nothing: 2 > 1 free -> None, nothing consumed
    assert a.alloc(2) is None
    assert a.free_pages == 1
    a.free(g1)
    assert a.free_pages == 3 and a.used_pages == 3
    # freshly freed pages are reused (LIFO) and conservation holds
    g3 = a.alloc(3)
    assert set(g1) <= set(g3)
    assert a.used_pages + a.free_pages == a.pool_pages == 6


def test_footprint_and_fits():
    rc = paged_cfg(max_prompt_bucket=16, max_new_cap=16, pool_pages=2)
    # prompt bucket 16 + 8 generated + 1 frozen-row slot = 25 -> 2 pages
    assert rc.page_footprint(16, 8) == 2
    assert rc.fits(Request(1, 0.0, prompt_len=12, max_new=8))
    # capacity would hold it, but the pool cannot: falls back to chunked
    assert not rc.fits(Request(2, 0.0, prompt_len=16, max_new=16))


# ----------------------------------------------------------- correctness

def test_paged_matches_dense_tokens(serving):
    """The paged slab must emit exactly the dense slab's greedy tokens —
    the layout is an optimization, not a model change."""
    reqs = lambda: [Request(i, 0.0, prompt_len=5 + i, max_new=2 + 3 * (i % 4))
                    for i in range(1, 9)]
    logs = {}
    for name, rcfg in (("dense", RuntimeConfig(max_batch=4, admit_tail=0,
                                               paged=False)),
                       ("paged", paged_cfg(admit_tail=0))):
        rt = mk_runtime(serving, rcfg, record_tokens=True)
        rt.submit(reqs())
        done = rt.pump()
        assert sorted(f.req.rid for f in done) == list(range(1, 9))
        logs[name] = dict(rt.token_log)
    assert logs["paged"] == logs["dense"]


def test_pool_exhaustion_blocks_admission_until_retirement(serving):
    """A pool smaller than the slot count's worst case: admission waits
    for retirements instead of over-committing, every request completes,
    and the high-water mark respects the pool."""
    rc = paged_cfg(pool_pages=6, max_prompt_bucket=16, max_new_cap=32)
    rt = mk_runtime(serving, rc)
    reqs = [Request(i, 0.0, prompt_len=10, max_new=12) for i in range(1, 9)]
    assert all(rt.fits(r) for r in reqs)     # each fits alone (2 pages)
    rt.submit(reqs)
    done = rt.pump()
    assert sorted(f.req.rid for f in done) == list(range(1, 9))
    assert all(f.tokens == f.req.max_new for f in done)
    assert rt.pages_hwm <= 6
    assert rt.alloc.used_pages == 0 and rt.alloc.free_pages == 6
    assert not rt.page_table.any()           # every row back on null pages


def test_reuse_after_midstream_retirement_no_fragmentation(serving):
    """Short requests retire mid-stream under longer ones; their pages are
    re-granted to later admissions (unit granularity = no stranded
    fragments) and the slot/allocator books always balance."""
    rc = paged_cfg(max_batch=2, decode_block=4, pool_pages=8,
                   max_prompt_bucket=16, max_new_cap=32)
    rt = mk_runtime(serving, rc)
    rt.submit([Request(1, 0.0, prompt_len=8, max_new=2),
               Request(2, 0.0, prompt_len=8, max_new=24),
               Request(3, 0.0, prompt_len=8, max_new=2),
               Request(4, 0.0, prompt_len=8, max_new=2)])
    done = []
    seen_pages = set()
    for _ in range(40):
        done.extend(rt.step())
        assert rt.alloc.used_pages == used_by_slots(rt)
        assert rt.alloc.used_pages + rt.alloc.free_pages == rc.n_pool_pages
        for s in rt.slots:
            if s.busy:
                seen_pages.update(s.pages)
        if not rt.inflight:
            break
    assert sorted(f.req.rid for f in done) == [1, 2, 3, 4]
    # the pool is smaller than the sum of footprints ever admitted, so
    # reuse must have happened for all four to complete
    total_footprint = sum(rc.page_footprint(8, mn) for mn in (2, 24, 2, 2))
    assert total_footprint > rc.n_pool_pages or len(seen_pages) < total_footprint


# ------------------------------------------------------------ checkpoint

def test_paged_checkpoint_roundtrip_token_identity(serving, tmp_path):
    """Page-table state through drain -> evict -> restore: the checkpoint
    carries the logical ledger (not physical page ids); the successor's
    admission re-allocates pages and replays token-identical output."""
    rc = paged_cfg(max_batch=2, admit_tail=0, decode_block=4)
    ref = mk_runtime(serving, rc, record_tokens=True)
    ref.submit([Request(1, 0.0, prompt_len=8, max_new=2),
                Request(2, 0.0, prompt_len=8, max_new=10)])
    ref.pump()
    ref_log = ref.token_log[2]

    rt = mk_runtime(serving, rc, record_tokens=True)
    rt.submit([Request(1, 0.0, prompt_len=8, max_new=2),
               Request(2, 0.0, prompt_len=8, max_new=10)])
    rt._admit_some()
    rt._decode_block()                      # r1 done, r2 mid-generation
    assert rt.alloc.used_pages == used_by_slots(rt) > 0
    state = rt.state()
    tree = {k: np.asarray(v) for k, v in state.items()}
    checkpointer.save(tmp_path, 0, tree, meta={"pod": "r0"})
    restored, _ = checkpointer.restore(tmp_path, tree, step=0)
    # predecessor drains: every page returns to its pool
    rt.drain()
    assert rt.alloc.used_pages == 0 and not rt.page_table.any()

    rt2 = mk_runtime(serving, rc, record_tokens=True)
    rt2.restore(restored)
    rt2.pump()
    assert rt2.alloc.used_pages == 0        # successor books balance too
    got = rt2.token_log[2]
    assert got == ref_log[:len(got)]        # token-identical replay
    assert len(got) == 7                    # 1 prefill argmax + 6 remaining


# ------------------------------------------------------------ trace bound

def test_paged_trace_counts_bounded_random_shapes(serving):
    rc = paged_cfg()
    rt = mk_runtime(serving, rc)
    rng = np.random.default_rng(9)
    rid = 0
    for _ in range(10):
        reqs = []
        for _ in range(int(rng.integers(1, 9))):
            rid += 1
            reqs.append(Request(rid, 0.0,
                                int(rng.integers(1, rc.max_prompt_bucket)),
                                int(rng.integers(1, 17))))
        rt.submit(reqs)
        for f in rt.pump():
            assert f.tokens == f.req.max_new
    traces = rt.kernels.trace_counts
    assert traces["admit"] + traces["decode"] <= rt.kernels.max_traces
    n_kv = len(rc.kv_ladder)
    assert traces["admit"] <= (len(rc.batch_buckets)
                               * len(rc.prompt_buckets) * n_kv)
    assert traces["decode"] <= len(rc.block_ladder) * n_kv


# -------------------------------------------------------- equal-HBM slots

def test_equal_hbm_pool_carries_more_concurrent_requests(serving):
    """The PagedAttention batch story: with the pool sized to the dense
    slab's KV entries, short-request footprints let 3x the slots run
    concurrently — impossible for the dense layout at the same HBM."""
    dense = RuntimeConfig(max_batch=4, paged=False)
    entries = (dense.max_batch + 1) * dense.capacity
    rc = paged_cfg(max_batch=12, pool_pages=entries // 16)
    rt = mk_runtime(serving, rc)
    rt.submit([Request(i, 0.0, prompt_len=6, max_new=12)
               for i in range(1, 13)])
    rt._admit_some()
    busy = sum(s.busy for s in rt.slots)
    assert busy == 12 > dense.max_batch
    assert rt.alloc.used_pages * rc.page_size <= entries
    done = rt.pump()
    assert sorted(f.req.rid for f in done) == list(range(1, 13))
