"""Serving runtime (PR 2): slot-slab continuous batching, bucketed
compilation (bounded jit traces), fused scan decode, fractional tick
budgets, compile-cache reuse on rescale, and slot-table checkpoint
round-trips through the drain -> reschedule loop."""
import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.configs.base import get_config
from repro.core.elastic import ElasticServing
from repro.core.jrm import SliceSpec, start_vk
from repro.data.pipeline import Request, RequestSource
from repro.models import model_api as MA
from repro.streaming.engine import StreamEngine
from repro.streaming.runtime import (DecodeRuntime, RuntimeConfig,
                                     RuntimeKernels, requests_from_state)


@pytest.fixture(scope="module")
def serving():
    cfg = get_config("qwen2-7b").reduced()
    return ElasticServing(cfg, tp=1).build(1, seed=0)


def mk_runtime(serving, rcfg=None, **kw):
    rcfg = rcfg or RuntimeConfig(max_batch=4)
    return DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                         gen=serving.build_gen, **kw)


def mk_engine(serving, n_nodes=1, **kw):
    nodes = [start_vk(f"n{i}", now=0.0, slice_spec=SliceSpec(chips=4))
             for i in range(n_nodes)]
    return StreamEngine(serving.cfg, serving, nodes, **kw)


# ------------------------------------------------------------ correctness

def test_runtime_matches_legacy_decode_tokens(serving):
    """With a bucket-exact prompt, the slab path must emit the same greedy
    tokens as the legacy prefill + per-token decode loop."""
    cfg = serving.cfg
    rcfg = RuntimeConfig(max_batch=2, admit_tail=0)
    rt = mk_runtime(serving, rcfg, record_tokens=True)
    req = Request(rid=1, arrival=0.0, prompt_len=8, max_new=6)
    rt.submit([req])
    done = rt.pump()
    assert [f.req.rid for f in done] == [1]
    got = rt.token_log[1]                       # first + 6 block tokens
    # legacy reference: same prompt tokens (the runtime's admission rng)
    rng = np.random.default_rng(hash((1, 8)) % (2 ** 31))
    toks = rng.integers(0, cfg.vocab, (1, 8)).astype(np.int32)
    logits, cache = serving.prefill_fn(serving.params, toks)
    cache = MA.grow_cache(cfg, cache, 8 + req.max_new + 1)
    tok = np.argmax(np.asarray(logits), -1)[:, None].astype(np.int32)
    ref = [int(tok[0, 0])]
    for _ in range(req.max_new):
        logits, cache = serving.decode_fn(serving.params, tok, cache)
        tok = np.argmax(np.asarray(logits), -1)[:, None].astype(np.int32)
        ref.append(int(tok[0, 0]))
    assert got[:len(ref)] == ref


def test_continuous_batching_exact_token_accounting(serving):
    """Every request generates exactly its own max_new — nobody rides
    along for a chunk-mate's longer generation."""
    rt = mk_runtime(serving)
    reqs = [Request(i, 0.0, prompt_len=5 + i, max_new=2 + 3 * (i % 4))
            for i in range(1, 11)]
    rt.submit(reqs)
    done = rt.pump()
    assert sorted(f.req.rid for f in done) == list(range(1, 11))
    for f in done:
        assert f.tokens == f.req.max_new
    assert rt.inflight == 0


def test_pump_drains_pending_when_tail_finishes_everything(serving):
    """Regression: requests shorter than the fused admission tail finish
    inside the admit dispatch itself; pump must still refill the freed
    slots until the pending queue is empty."""
    rcfg = RuntimeConfig(max_batch=2, admit_tail=4)
    rt = mk_runtime(serving, rcfg)
    rt.submit([Request(i, 0.0, prompt_len=6, max_new=3) for i in (1, 2, 3)])
    done = rt.pump()
    assert sorted(f.req.rid for f in done) == [1, 2, 3]
    assert rt.inflight == 0


# --------------------------------------------------- bucketed compilation

def test_trace_count_bounded_under_random_shapes(serving):
    """Regression guard: random (batch, prompt_len, max_new) mixes must
    not grow the jit trace count past the bucket bound."""
    rcfg = RuntimeConfig(max_batch=4)
    rt = mk_runtime(serving, rcfg)
    kern = rt.kernels
    rng = np.random.default_rng(3)
    rid = 0
    for round_ in range(12):
        n = int(rng.integers(1, 9))
        reqs = []
        for _ in range(n):
            rid += 1
            reqs.append(Request(rid, 0.0,
                                int(rng.integers(1, rcfg.max_prompt_bucket)),
                                int(rng.integers(1, 17))))
        rt.submit(reqs)
        for f in rt.pump():
            assert f.tokens == f.req.max_new
    traces = kern.trace_counts
    assert traces["admit"] >= 1 and traces["decode"] >= 1
    n_bb = len(rcfg.batch_buckets)
    n_lb = len(rcfg.prompt_buckets)
    # paged decode adds the kv-read-bucket dimension; the dense slab adds
    # the host-adaptive plain/block-skip pair per fused-step bucket
    n_kv = len(rcfg.kv_ladder) if rcfg.paged else 1
    n_skip = 1 if rcfg.paged or not rcfg.block_skip else 2
    assert traces["admit"] <= n_bb * n_lb * n_kv
    assert traces["decode"] <= len(rcfg.block_ladder) * n_kv * n_skip
    assert traces["admit"] + traces["decode"] <= kern.max_traces


def test_kernels_cached_across_runtimes_and_rescale(serving):
    """Replica runtimes share one kernel set per topology; re-building the
    serving mesh at a seen size reuses both the jitted prefill/decode and
    the runtime kernels (no re-lowering on scale oscillation)."""
    rcfg = RuntimeConfig(max_batch=4)
    k1 = serving.runtime_kernels(rcfg)
    k2 = serving.runtime_kernels(rcfg)
    assert k1 is k2
    pf, df = serving.prefill_fn, serving.decode_fn
    serving.build(serving.replicas)            # same (replicas, tp)
    assert serving.prefill_fn is pf and serving.decode_fn is df
    assert serving.runtime_kernels(rcfg) is k1


def test_oversize_requests_fall_back(serving):
    rcfg = RuntimeConfig(max_batch=4, max_prompt_bucket=16, max_new_cap=8)
    rt = mk_runtime(serving, rcfg)
    assert rt.fits(Request(1, 0.0, prompt_len=12, max_new=4))
    assert not rt.fits(Request(2, 0.0, prompt_len=99, max_new=4))
    assert not rt.fits(Request(3, 0.0, prompt_len=16, max_new=99))


# ------------------------------------------------------------- checkpoint

def test_slot_table_checkpoint_roundtrip(serving, tmp_path):
    """Mid-stream slot state survives save/restore through repro.checkpoint
    (the §4.5.4 drain path): a fresh runtime resumes the remainder and
    partial credit + finish credit sum to exactly max_new per request."""
    rt = mk_runtime(serving, RuntimeConfig(max_batch=2, decode_block=4))
    reqs = [Request(i, 0.5 * i, prompt_len=6, max_new=10) for i in (1, 2, 3)]
    rt.submit(reqs)
    done1 = rt.step()                           # partial progress only
    assert rt.inflight > 0
    state = rt.state()
    partial = rt.partial_tokens()
    tree = {k: np.asarray(v) for k, v in state.items()}
    checkpointer.save(tmp_path, 0, tree, meta={"pod": "r0"})
    restored, _ = checkpointer.restore(tmp_path, tree, step=0)

    rt2 = mk_runtime(serving, RuntimeConfig(max_batch=2, decode_block=4))
    rt2.restore(restored)
    done2 = rt2.pump()
    rids = sorted([f.req.rid for f in done1] + [f.req.rid for f in done2])
    assert rids == [1, 2, 3]                    # zero request loss
    # arrival timestamps survive (latency metrics stay truthful)
    by_rid = {f.req.rid: f.req for f in done2}
    for r in reqs:
        if r.rid in by_rid:
            assert by_rid[r.rid].arrival == pytest.approx(r.arrival)
    total = (partial + sum(f.tokens for f in done1)
             + sum(f.tokens for f in done2))
    assert total == sum(r.max_new for r in reqs)


def test_restored_rid_replays_exact_prompt_tokens(serving, tmp_path):
    """ROADMAP follow-up: restored requests used to re-randomize their
    prompts (admission tokens were seeded by the *group*'s first rid, so
    a rid restored into a different grouping got a different prompt). The
    content store pins each rid's prompt at first admission and rides the
    checkpoint: greedy output across a drain is a token-identical replay
    of the undisturbed run."""
    rcfg = RuntimeConfig(max_batch=2, admit_tail=0, decode_block=4)
    # undisturbed reference: both requests admitted as one group
    ref = mk_runtime(serving, rcfg, record_tokens=True)
    ref.submit([Request(1, 0.0, prompt_len=8, max_new=2),
                Request(2, 0.0, prompt_len=8, max_new=10)])
    ref.pump()
    ref_log = ref.token_log[2]
    assert len(ref_log) == 11                   # first + max_new tokens

    # drained run: r1 finishes in the first block; r2 is checkpointed
    # mid-generation and restored SOLO — the admission grouping changes,
    # the prompt must not
    rt = mk_runtime(serving, rcfg, record_tokens=True)
    rt.submit([Request(1, 0.0, prompt_len=8, max_new=2),
               Request(2, 0.0, prompt_len=8, max_new=10)])
    rt._admit_some()
    rt._decode_block()                          # r1 done, r2 has 6 left
    state = rt.state()
    assert int(state["content_len"][0]) == 8    # prompt rides the ckpt
    tree = {k: np.asarray(v) for k, v in state.items()}
    checkpointer.save(tmp_path, 0, tree, meta={"pod": "r0"})
    restored, _ = checkpointer.restore(tmp_path, tree, step=0)

    rt2 = mk_runtime(serving, rcfg, record_tokens=True)
    rt2.restore(restored)
    assert np.array_equal(rt2.content[2], rt.content[2])
    rt2.pump()
    assert 2 not in rt2.content     # store pruned once the rid finishes
    # the restored incarnation re-prefills the exact prompt: its greedy
    # stream is a prefix replay of the undisturbed run (1 + 6 tokens)
    got = rt2.token_log[2]
    assert got == ref_log[:len(got)]
    assert len(got) == 7


def test_requests_from_state_empty():
    assert requests_from_state({}) == []
    rt_state = {"inflight_rid": np.zeros(0, np.int64),
                "inflight_arrival": np.zeros(0),
                "inflight_plen": np.zeros(0, np.int64),
                "inflight_remaining": np.zeros(0, np.int64)}
    assert requests_from_state(rt_state) == []


def test_engine_drain_checkpoints_inflight_slots(serving, tmp_path):
    """End-to-end: a replica with mid-stream slots on a draining node is
    checkpointed; the rescheduled replica's runtime resumes the slot table
    and every request completes."""
    nodes = [start_vk("doomed", walltime=100.0, now=0.0,
                      slice_spec=SliceSpec(chips=4)),
             start_vk("healthy", now=0.0, slice_spec=SliceSpec(chips=4))]
    eng = StreamEngine(serving.cfg, serving, nodes, service_rate=50.0,
                       max_batch=4)
    eng._ensure_plane(0.0)
    # pin the replica onto the short-lease node
    eng.plane.scheduler.scorers = [
        lambda rec, node, sched, now: 1.0 if node.name == "doomed" else 0.0]
    eng.deploy(0.0)
    eng.plane.nodes.ckpt_dir = str(tmp_path)
    (name, rt), = eng.runtimes.items()
    assert eng.pods[name].node == "doomed"
    # park mid-stream work in the replica's slots (partial progress only:
    # admission + its fused tail, no full decode blocks)
    rt.submit([Request(101, 0.0, prompt_len=6, max_new=12),
               Request(102, 0.0, prompt_len=6, max_new=12)])
    rt._admit_some()
    assert rt.inflight == 2 and rt.partial_tokens() > 0
    # node enters its drain margin -> checkpoint, evict, reschedule
    now = 70.0
    eng.plane.scheduler.scorers = []
    for n in eng.cluster.nodes:
        eng.cluster.heartbeat(n, now)
    eng.reconcile(now)
    moved = [r for r in eng.cluster.pods_of("ersap") if r.restored_from]
    assert moved and moved[0].pod.node == "healthy"
    assert np.asarray(moved[0].restored_state["inflight_rid"]).size == 2
    # exactly one live copy of each in-flight request (the retire path and
    # the checkpoint restore both name the same rids — no double-serving)
    new_rt = eng.runtimes[moved[0].name]
    carried = ([r.rid for r in eng.queue] + [r.rid for r in new_rt.pending]
               + [s.req.rid for s in new_rt.slots if s.busy])
    assert sorted(carried) == [101, 102]
    eng.tick(now + 1.0, 1.0, lam=0.0)
    assert sorted(rid for rid, _ in eng.completed) == [101, 102]
    # partial + finish-time credit sums to exactly max_new per request
    assert eng.total_tokens == 24


# ------------------------------------------------------ engine satellites

def test_fractional_budget_no_starvation(serving):
    """service_rate * dt < 1 used to truncate to a 0 budget forever; the
    fractional carry must eventually serve the queue."""
    eng = mk_engine(serving, service_rate=0.3, max_batch=4)
    eng.deploy(0.0)
    eng.queue.extend(
        Request(i, 0.0, prompt_len=8, max_new=2) for i in range(1, 4))
    for t in range(12):
        eng.tick(float(t), 1.0, lam=0.0)
    assert eng.total_served == 3 and not eng.queue
    # carry stays a proper fraction (no unbounded accumulation)
    assert 0.0 <= eng._budget_frac < 1.0


def test_cp_ports_pruned_with_pods(serving):
    """The §4.6.3 control-plane port map follows the live pod set across
    scale/evict cycles instead of growing monotonically."""
    eng = mk_engine(serving, n_nodes=2, service_rate=5.0)
    eng.deploy(0.0)
    for i in range(4):
        eng.cluster.scale("ersap", 2, float(i), source="test")
        eng.reconcile(float(i))
        eng.cluster.scale("ersap", 1, float(i) + 0.5, source="test")
        eng.reconcile(float(i) + 0.5)
    assert set(eng._cp_ports) == set(eng.pods)
    assert len(eng._cp_ports) == 1


def test_engine_runtime_serves_varied_shapes(serving):
    """Engine + runtime under randomized request shapes: everything
    completes, token totals are exact, traces stay bounded."""
    eng = mk_engine(serving, service_rate=30.0, max_batch=4)
    eng.source = RequestSource(seed=5, prompt_range=(4, 40),
                               max_new_range=(1, 12))
    eng.deploy(0.0)
    for t in range(4):
        eng.tick(t * 1.0, 1.0, lam=8.0)
    eng.tick(5.0, 1.0, lam=0.0)
    assert eng.total_served == eng.source.rid > 0
    assert len(eng.completed) == eng.source.rid
    rt = next(iter(eng.runtimes.values()))
    assert (rt.kernels.trace_counts["admit"]
            + rt.kernels.trace_counts["decode"]) <= rt.kernels.max_traces
