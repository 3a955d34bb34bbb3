"""Prefix-sharing copy-on-write paged KV + speculative decode (PR 6):
refcount allocator semantics, splice-vs-prefill token identity (cross-wave
and same-wave sharing), CoW forking on sub-page prompts, randomized
refcount-books interleavings, drain -> restore sharing survival, and
k-token speculative decode equivalence. Every identity test compares
against the prefix-off (or spec-off) oracle on the same requests — the
sharing layer is an admission optimization, never a model change."""
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


def prefix_cfg(**kw):
    base = dict(max_batch=4, paged=True, page_size=16, admit_tail=0,
                prefix_cache=True)
    base.update(kw)
    return RuntimeConfig(**base)


def grouped(rid, plen, mnew, group):
    """A request carrying a template group's full prompt."""
    return Request(rid, 0.0, plen, mnew, prefix_group=group,
                   prefix_len=plen)


def oracle_log(serving, rcfg, reqs):
    """Greedy tokens of the same requests with sharing disabled."""
    import dataclasses
    off = dataclasses.replace(rcfg, prefix_cache=False, spec_decode=0)
    rt = mk_runtime(serving, off, record_tokens=True)
    rt.submit(list(reqs))
    rt.pump()
    return dict(rt.token_log)


# ------------------------------------------------------------- allocator

def test_refcount_share_free():
    a = PageAllocator(8)
    g = a.alloc(3)
    assert list(np.asarray(a.refcount)[g]) == [1, 1, 1]
    a.share(g[:2])                           # second holder splices
    assert list(np.asarray(a.refcount)[g]) == [2, 2, 1]
    assert a.shared_pages == 2
    # first free only decrements shared pages; the private one releases
    released = a.free(g)
    assert released == [g[2]]
    assert a.used_pages == 2 and a.shared_pages == 0
    # second free releases the rest; books balance, nothing double-freed
    released = a.free(g[:2])
    assert sorted(released) == sorted(g[:2])
    assert a.used_pages == 0 and a.free_pages == a.pool_pages == 8
    with pytest.raises(AssertionError):
        a.share([g[0]])                      # sharing a free page is a bug


# --------------------------------------------------- sharing correctness

def test_e2e_sharing_token_identity_any_mode(serving):
    """Small end-to-end sharing run honoring the ambient KERNEL_MODE (the
    CI pallas leg runs exactly this test in interpret mode): a second
    wave splices the first wave's still-referenced prompt pages and every
    token matches the no-sharing oracle."""
    rc = prefix_cfg(max_batch=2, decode_block=4)
    rt = mk_runtime(serving, rc, record_tokens=True)
    wave_a = [grouped(1, 16, 8, group=1)]
    wave_b = [grouped(2, 16, 2, group=1)]    # same template, later arrival
    rt.submit(wave_a)
    rt.step()                                # A admitted, still in flight
    rt.submit(wave_b)
    rt.pump()
    assert rt.prefix_hits == 1
    assert rt.token_log == oracle_log(serving, rc, wave_a + wave_b)
    assert rt.alloc.used_pages == 0 and not rt.page_table.any()


def test_same_wave_sharing_token_identity(serving):
    """One submission wave containing a template group: the leader
    prefills, same-wave mates splice its pages before it ever reaches the
    intern table (wave-local publication). Tokens match the oracle and
    the wave shares pages while in flight."""
    rc = prefix_cfg()
    reqs = [grouped(1, 32, 6, 1), grouped(2, 32, 4, 1),
            grouped(3, 32, 6, 2), Request(4, 0.0, 32, 5)]
    rt = mk_runtime(serving, rc, record_tokens=True)
    rt.submit(reqs)
    rt._admit_some()
    assert rt.prefix_hits == 1               # rid 2 follows rid 1's grant
    assert rt.shared_pages > 0
    rt.pump()
    assert rt.token_log == oracle_log(serving, rc, reqs)
    assert rt.alloc.used_pages == 0


def test_partial_prefix_tail_admission(serving):
    """Shared page-aligned prefix with distinct tails: the hit splices
    the prefix pages and prefills only the remainder (a window dispatch,
    not a full prefill). Requires prompts spanning >1 page."""
    rc = prefix_cfg(max_batch=4, max_prompt_bucket=64, decode_block=4)
    # same 16-token template head, unique continuations; the leader's
    # max_new outlasts one decode block so its pages stay referenced
    mk = lambda rid: Request(rid, 0.0, 40, 12, prefix_group=3, prefix_len=16)
    reqs = [mk(1), mk(2)]
    rt = mk_runtime(serving, rc, record_tokens=True)
    rt.submit([reqs[0]])
    rt.step()
    rt.submit([reqs[1]])
    rt.pump()
    assert rt.prefix_hits == 1
    assert rt.kernels.trace_counts["window"] >= 1    # tail prefill ran
    assert rt.token_log == oracle_log(serving, rc, reqs)


def test_cow_forks_writer_not_readers(serving):
    """Sub-page prompt (8 tokens, 16-token pages): both holders decode
    into the shared boundary page, so the first writer must fork onto its
    reserve page while the reader keeps the original — structurally
    visible (the rows end up on different physical pages) and
    token-identical to the no-sharing oracle."""
    rc = prefix_cfg(max_batch=2, decode_block=4)
    reqs = [grouped(1, 8, 8, 1), grouped(2, 8, 6, 1)]
    rt = mk_runtime(serving, rc, record_tokens=True)
    rt.submit([reqs[0]])
    rt._admit_some()
    rt.submit([reqs[1]])
    rt._admit_some()
    pages0 = [s.pages[0] for s in rt.slots if s.busy]
    assert pages0[0] == pages0[1]            # boundary page shared
    assert rt.prefix_hits == 1
    rt._decode_block()                       # first write past the prompt
    pages1 = [s.pages[0] for s in rt.slots if s.busy]
    assert pages1[0] != pages1[1]            # writer forked, reader kept
    assert rt.cow_events >= 1
    rt.pump()
    assert rt.token_log == oracle_log(serving, rc, reqs)
    assert rt.alloc.used_pages == 0


# ------------------------------------------------------ refcount property

def test_refcount_books_random_interleavings(serving):
    """Seeded randomized admit/decode/retire/drain interleavings (the
    vendored-property-test posture: no hypothesis dependency). After
    every step: used + free == pool, page 0 unreferenced, and each page's
    refcount equals the number of slots holding it (pages + CoW reserve)
    — intern entries hold no references of their own."""
    rc = prefix_cfg(max_batch=4, decode_block=4, max_prompt_bucket=32,
                    max_new_cap=16, pool_pages=48)
    rt = mk_runtime(serving, rc)
    rng = np.random.default_rng(42)
    rid = 0

    def assert_books():
        a = rt.alloc
        assert a.used_pages + a.free_pages == rc.n_pool_pages
        holders = np.zeros(a.n_pages, np.int64)
        for s in rt.slots:
            if s.busy:
                for p in s.pages:
                    holders[p] += 1
                if s.reserve is not None:
                    holders[s.reserve] += 1
        assert holders[0] == 0               # null page never granted
        np.testing.assert_array_equal(np.asarray(a.refcount)[1:],
                                      holders[1:])
        for e in rt._intern.values():        # interned pages are live
            assert all(np.asarray(a.refcount)[list(e["pages"])] > 0)

    for round_ in range(30):
        op = rng.random()
        if op < 0.5 or not rt.inflight:
            n = int(rng.integers(1, 4))
            reqs = []
            for _ in range(n):
                rid += 1
                group = int(rng.integers(0, 3))
                plen = int(rng.choice([8, 16, 24, 32]))
                reqs.append(Request(rid, 0.0, plen,
                                    int(rng.integers(1, 9)),
                                    prefix_group=group,
                                    prefix_len=plen if group else 0))
            rt.submit(reqs)
            rt.step()
        elif op < 0.9:
            rt.step()
        else:
            carried = rt.drain()             # §4.5.4 eviction wave
            assert rt.alloc.used_pages == 0
            assert not rt.page_table.any()
            assert_books()
            rt.submit(carried)               # re-admission re-mints
            rt.step()
        assert_books()
    while rt.inflight:
        rt.step()
        assert_books()
    assert rt.alloc.used_pages == 0


# ------------------------------------------------------- drain -> restore

def test_drain_restore_preserves_sharing(serving, tmp_path):
    """Checkpoint mid-stream with two rows sharing a template prompt: the
    successor re-interns the prefix on re-admission (content-hash
    identity, not physical page ids), so sharing survives the move and
    the replay is token-identical to an uninterrupted run."""
    rc = prefix_cfg(max_batch=2, decode_block=4)
    reqs = [grouped(1, 16, 10, 1), grouped(2, 16, 8, 1)]
    ref = mk_runtime(serving, rc, record_tokens=True)
    ref.submit(list(reqs))
    ref.pump()

    rt = mk_runtime(serving, rc, record_tokens=True)
    rt.submit(list(reqs))
    rt._admit_some()
    rt._decode_block()                       # both mid-generation
    assert rt.shared_pages > 0
    state = rt.state()
    tree = {k: np.asarray(v) for k, v in state.items()}
    checkpointer.save(tmp_path, 0, tree, meta={"pod": "r0"})
    restored, _ = checkpointer.restore(tmp_path, tree, step=0)
    rt.drain()
    assert rt.alloc.used_pages == 0

    rt2 = mk_runtime(serving, rc, record_tokens=True)
    rt2.restore(restored)
    rt2._admit_some()
    assert rt2.prefix_hits >= 1              # re-admission re-shared
    assert rt2.shared_pages > 0
    rt2.pump()
    assert rt2.alloc.used_pages == 0
    for r in reqs:                           # token-identical replay (the
        got = rt2.token_log[r.rid]           # PR-4 prefix-replay contract)
        assert got and got == ref.token_log[r.rid][:len(got)]


# ------------------------------------------------------ speculative decode

def test_spec_decode_token_identity(serving):
    """spec_decode=k emits exactly the one-token-at-a-time greedy stream
    (accept-prefix verification), and on replay traffic — identical
    prompts served after a paver completed — the stream drafter actually
    accepts (the speedup mechanism, not just a fallback)."""
    rc = prefix_cfg(max_batch=4, spec_decode=3)
    paver = [grouped(1, 16, 12, 1)]
    replay = [grouped(10 + j, 16, 12, 1) for j in range(3)]
    rt = mk_runtime(serving, rc, record_tokens=True)
    rt.submit(list(paver))
    rt.pump()
    d0, a0 = rt.spec_drafted, rt.spec_accepted
    rt.submit(list(replay))
    rt.pump()
    assert rt.spec_rounds > 0
    # replay-phase drafts come from the paver's recorded stream and mostly
    # accept (the paver itself had nothing to draft from — excluded)
    assert (rt.spec_accepted - a0) / (rt.spec_drafted - d0) > 0.5
    # spec verify must dispatch fewer rounds than tokens emitted
    assert rt.spec_emitted > rt.spec_rounds
    assert rt.token_log == oracle_log(serving, rc, paver + replay)


def test_spec_requires_tail_free_admission(serving):
    with pytest.raises(ValueError):
        serving.runtime_kernels(
            RuntimeConfig(paged=True, spec_decode=2, admit_tail=4))


def test_prefix_and_spec_require_paged(serving):
    for bad in (RuntimeConfig(paged=False, prefix_cache=True),
                RuntimeConfig(paged=False, spec_decode=2, admit_tail=0)):
        with pytest.raises(ValueError):
            serving.runtime_kernels(bad)
