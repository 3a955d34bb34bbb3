"""Overload protection & graceful degradation (ISSUE-9): deadline-aware
admission, brownout watermarks with hysteresis, retry budgets, replica
circuit breaking, ring-capped logs, and the cost-modeled failover window.

Invariants under test: the brownout level never flaps inside the
hysteresis dead band and recovers in stages; retry budgets exhaust and
refill as token buckets; an ejected replica rejoins only through a
healthy half-open probe; a request whose deadline expired in the queue
never reaches prefill; bounded-queue backpressure loses nothing (every
request completes exactly once or is an explicit shed with a reason);
degraded service caps output length without changing token *content*
(prefix of the unloaded oracle's stream); ring caps truncate with
explicit markers; drain_site pays the topology's transfer window and the
engine serves degraded for its duration."""
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.core import qos
from repro.core.chaos import FaultInjector, FaultSpec
from repro.core.cluster import Cluster
from repro.core.elastic import ElasticServing
from repro.core.jrm import SliceSpec, start_vk
from repro.core.scheduler import Scheduler, SiteTopology
from repro.data.pipeline import Request, RequestSource
from repro.streaming.engine import StreamEngine
from repro.streaming.runtime import DecodeRuntime, RuntimeConfig


@pytest.fixture(scope="module")
def serving():
    cfg = get_config("qwen2-7b").reduced()
    return ElasticServing(cfg, tp=1).build(1, seed=0)


def mk_engine(serving, n_nodes=1, **kw):
    nodes = [start_vk(f"n{i}", now=0.0, slice_spec=SliceSpec(chips=4))
             for i in range(n_nodes)]
    kw.setdefault("runtime_cfg", RuntimeConfig(max_batch=4, admit_tail=0))
    return StreamEngine(serving.cfg, serving, nodes, **kw)


# ------------------------------------------------------ brownout controller

def test_brownout_escalates_only_after_dwell():
    bc = qos.BrownoutController(dwell_ticks=3)
    for i in range(2):
        assert bc.update(float(i), 0.95, 0.0) == 0
    assert bc.update(2.0, 0.95, 0.0) == 1
    # counter restarts per level: two more high ticks are not enough
    assert bc.update(3.0, 0.95, 0.0) == 1
    assert bc.update(4.0, 0.95, 0.0) == 1
    assert bc.update(5.0, 0.95, 0.0) == 2


def test_brownout_dead_band_holds_level_no_flap():
    bc = qos.BrownoutController(high_water=0.85, low_water=0.5,
                                dwell_ticks=2, recover_ticks=2)
    bc.level = 2
    # oscillate inside the dead band (and touch each watermark once,
    # never consecutively): the level must hold and nothing transitions
    for i, p in enumerate([0.7, 0.86, 0.7, 0.49, 0.7, 0.86, 0.7]):
        assert bc.update(float(i), p, 0.0) == 2
    assert bc.transitions == []


def test_brownout_staged_recovery_never_snaps_to_zero():
    bc = qos.BrownoutController(recover_ticks=2)
    bc.level = 3
    levels = [bc.update(float(i), 0.0, 0.0) for i in range(6)]
    # one level per recover_ticks — 3 -> 2 -> 1 -> 0, never 3 -> 0
    assert levels == [3, 2, 2, 1, 1, 0]
    assert [(old, new) for _, old, new, _ in bc.transitions] == \
        [(3, 2), (2, 1), (1, 0)]


def test_brownout_delay_ewma_drives_pressure():
    bc = qos.BrownoutController(delay_target_s=10.0, ewma_alpha=1.0,
                                dwell_ticks=1)
    assert bc.update(0.0, 0.0, 30.0) == 1          # delay 3x target
    assert bc.last_pressure == pytest.approx(3.0)


def test_brownout_degrade_knobs():
    bc = qos.BrownoutController(degrade_max_new=4)
    assert bc.max_new_cap() is None and bc.spec_enabled()
    bc.level = 1
    assert bc.max_new_cap() == 4 and not bc.spec_enabled()
    assert bc.shed_floor() == 0
    bc.level = 2
    assert bc.shed_floor() == qos.STANDARD.value
    bc.level = 3
    assert bc.shed_floor() == qos.LATENCY_CRITICAL.value


def test_tier_label_maps_to_highest_class_at_or_below():
    assert qos.tier_label(0) == "batch"
    assert qos.tier_label(10) == "standard"
    assert qos.tier_label(55) == "standard"
    assert qos.tier_label(100) == "latency-critical"
    assert qos.tier_label(5000) == "system"


# ----------------------------------------------------------- retry budgets

def test_retry_budget_exhausts_then_refills():
    rb = qos.RetryBudget(rate=1.0, burst=3.0)
    assert all(rb.allow("standard", 0.0) for _ in range(3))
    assert not rb.allow("standard", 0.0)           # bucket dry
    assert rb.granted == 3 and rb.denied == 1
    # tenants are isolated: another tenant still has its full burst
    assert rb.allow("batch", 0.0)
    # refill at ``rate``/s — 2 seconds buys 2 retries
    assert rb.allow("standard", 2.0)
    assert rb.allow("standard", 2.0)
    assert not rb.allow("standard", 2.0)


# --------------------------------------------------------- replica breaker

def test_breaker_ejects_probes_and_rejoins():
    br = qos.ReplicaBreaker(stall_ticks=2, probe_after_s=10.0,
                            probe_budget=2)
    assert br.allow("r0", 0.0) == -1               # closed: unbounded
    br.observe("r0", 0.0, 0, had_work=True)        # stall 1
    br.observe("r0", 1.0, 0, had_work=True)        # stall 2 -> eject
    assert br.state("r0") == qos.BREAKER_OPEN and br.ejections == 1
    assert br.allow("r0", 5.0) == 0                # still cooling off
    assert br.allow("r0", 11.0) == 2               # half-open: probes only
    br.note_probe("r0", 2)
    assert br.allow("r0", 12.0) == 0               # probe budget consumed
    br.observe("r0", 12.0, 5, had_work=True)       # healthy probe
    assert br.state("r0") == qos.BREAKER_CLOSED and br.rejoins == 1


def test_breaker_failed_probe_reopens():
    br = qos.ReplicaBreaker(stall_ticks=1, probe_after_s=10.0)
    br.observe("r0", 0.0, 0, had_work=True)
    assert br.state("r0") == qos.BREAKER_OPEN
    assert br.allow("r0", 10.0) > 0                # half-open
    br.observe("r0", 10.0, 0, had_work=True)       # stalled probe
    assert br.state("r0") == qos.BREAKER_OPEN
    # idle ticks (no work routed) never resolve a probe or count stalls
    br.allow("r0", 20.0)
    br.observe("r0", 20.0, 0, had_work=False)
    assert br.state("r0") == qos.BREAKER_HALF_OPEN


# ------------------------------------------------- source: surge + deferral

def test_surge_fault_scales_arrivals_within_window():
    src = RequestSource(seed=3)
    inj = FaultInjector([FaultSpec("surge", 10.0, "ersap", duration=20.0,
                                   magnitude=5.0)], seed=0)
    cluster = Cluster()
    counts = {}
    for t in range(6):
        now = t * 10.0
        inj.apply(cluster, now)
        src.surge = inj.surge_factor("ersap")
        counts[t] = (src.surge, len(src.arrivals(now, 10.0, 2.0)))
    assert counts[0][0] == 1.0
    assert counts[1][0] == 5.0 and counts[2][0] == 5.0
    assert counts[4][0] == 1.0                     # window expired
    # the surge factor targets by owner: another owner is untouched
    assert inj.surge_factor("other") == 1.0 or not inj.active


def test_defer_consumes_no_rng_and_releases_on_time():
    a = RequestSource(seed=7, tiers=((0, 0.5), (100, 0.5)))
    b = RequestSource(seed=7, tiers=((0, 0.5), (100, 0.5)))
    out_a = a.arrivals(0.0, 10.0, 1.0)
    out_b = b.arrivals(0.0, 10.0, 1.0)
    assert [r.rid for r in out_a] == [r.rid for r in out_b]
    # b defers two requests for retry; a drops them on the floor
    b.defer(out_b[:2], not_before=15.0)
    next_a = a.arrivals(10.0, 10.0, 1.0)
    next_b = b.arrivals(10.0, 10.0, 1.0)           # 15.0 not reached
    assert [(r.rid, r.priority) for r in next_a] == \
        [(r.rid, r.priority) for r in next_b]
    released = b.arrivals(20.0, 10.0, 0.0)
    assert [r.rid for r in released[:2]] == [r.rid for r in out_b[:2]]
    assert b.deferred_total == 2


def test_source_stamps_deadline_and_tiers():
    src = RequestSource(seed=1, ttl=30.0, tiers=((0, 1.0),))
    out = src.arrivals(0.0, 10.0, 5.0)
    assert out
    for r in out:
        assert r.deadline == pytest.approx(r.arrival + 30.0)
        assert r.priority == 0
    # ttl=0 keeps the no-deadline default
    assert RequestSource(seed=1).arrivals(0.0, 10.0, 5.0)[0].deadline == 0.0


# ----------------------------------------------------------- ring buffers

def test_cluster_event_ring_cap_truncates_with_marker():
    cluster = Cluster(events_cap=50)
    for i in range(120):
        cluster.record(float(i), "Pod", f"p{i}", "Tick", "")
    assert len(cluster.events) == 50
    assert cluster.events_truncated == 70
    assert cluster.events[0].name == "p70"         # oldest dropped first


def test_token_log_ring_cap_keeps_tail(serving):
    rcfg = RuntimeConfig(max_batch=2, admit_tail=0)
    full = DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                         gen=serving.build_gen, record_tokens=True)
    capped = DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                           gen=serving.build_gen, record_tokens=True,
                           token_log_cap=4)
    req = Request(rid=1, arrival=0.0, prompt_len=8, max_new=10)
    for rt in (full, capped):
        rt.submit([Request(**req.__dict__)])
        rt.pump()
    n_full = len(full.token_log[1])
    assert n_full > 4                              # cap actually binds
    assert len(capped.token_log[1]) == 4
    dropped = capped.token_log_dropped[1]
    assert dropped == n_full - 4                   # explicit marker
    assert list(capped.token_log[1]) == list(full.token_log[1])[dropped:]


# ------------------------------------------------- engine: admission + shed

def test_deadline_expired_in_queue_never_reaches_prefill(serving):
    eng = mk_engine(serving, service_rate=50.0, record_tokens=True)
    eng.deploy(0.0)
    dead = Request(rid=901, arrival=0.0, prompt_len=8, max_new=4,
                   deadline=5.0)
    live = Request(rid=902, arrival=0.0, prompt_len=8, max_new=4,
                   deadline=100.0)
    eng.queue.extend([dead, live])
    eng.tick(10.0, 1.0, lam=0.0)
    assert [rid for rid, _ in eng.completed] == [902]
    assert (901, "deadline", 10.0) in eng.shed
    assert eng.shed_counts == {"deadline": 1}
    for rt in eng.runtimes.values():
        assert 901 not in rt.token_log             # never prefilled


def test_bounded_queue_backpressure_defers_then_serves(serving):
    eng = mk_engine(serving, service_rate=2.0, record_tokens=True)
    eng.queue_cap = 4
    eng.deploy(0.0)
    src = eng.source
    # one burst far past the cap, then silence: overflow must defer
    # through the source and be served later — zero loss, no duplicates
    eng.tick(0.0, 1.0, lam=40.0)
    assert eng.rejected_total > 0 and eng.retried_total > 0
    assert len(eng.queue) <= eng.queue_cap
    for t in range(1, 40):
        eng.tick(float(t), 1.0, lam=0.0)
    done = [rid for rid, _ in eng.completed]
    assert len(done) == len(set(done)) == src.rid
    assert not src._deferred and not eng.shed


def test_backpressure_rejects_lowest_tier_first(serving):
    eng = mk_engine(serving, service_rate=1.0)
    eng.queue_cap = 2
    eng.deploy(0.0)
    lc = Request(rid=1, arrival=0.0, prompt_len=8, max_new=2, priority=100)
    std = Request(rid=2, arrival=0.0, prompt_len=8, max_new=2, priority=10)
    bat = Request(rid=3, arrival=0.0, prompt_len=8, max_new=2, priority=0)
    eng.source.arrivals = lambda now, dt, lam, **kw: [bat, std, lc]
    eng.tick(0.0, 1.0, lam=1.0)
    # room for two: latency-critical and standard admitted, batch deferred
    assert eng.rejected_total == 1
    queued = {r.rid for r in eng.queue} | \
        {rid for rid, _ in eng.completed} | \
        {s.req.rid for rt in eng.runtimes.values()
         for s in rt.slots if s.busy} | \
        {r.rid for rt in eng.runtimes.values() for r in rt.pending}
    assert {1, 2} <= queued and 3 not in queued


def test_retry_budget_dry_sheds_instead_of_retry_storm(serving):
    eng = mk_engine(serving, service_rate=1.0)
    eng.queue_cap = 1
    eng.retry_budget = qos.RetryBudget(rate=0.0, burst=1.0)
    eng.deploy(0.0)
    reqs = [Request(rid=i, arrival=0.0, prompt_len=8, max_new=2,
                    priority=10) for i in range(1, 5)]
    eng.source.arrivals = lambda now, dt, lam, **kw: list(reqs)
    eng.tick(0.0, 1.0, lam=1.0)
    # one deferred on the single budget token, the rest shed explicitly
    assert eng.retried_total == 1
    assert eng.shed_counts.get("retry-budget") == 2
    assert eng.retry_budget.denied == 2


def test_brownout_degrades_before_dropping(serving):
    """Level 1 must cap output length and disable speculative decode —
    and the capped stream must be a prefix of the uncapped one."""
    oracle = mk_engine(serving, service_rate=50.0, record_tokens=True)
    oracle.deploy(0.0)
    oracle.queue.append(Request(rid=7, arrival=0.0, prompt_len=8,
                                max_new=12))
    oracle.tick(0.0, 1.0, lam=0.0)
    o_log = [list(rt.token_log[7]) for rt in oracle.runtimes.values()
             if 7 in rt.token_log][0]

    eng = mk_engine(serving, service_rate=50.0, record_tokens=True)
    eng.brownout = qos.BrownoutController(degrade_max_new=3)
    eng.brownout.level = 1
    eng.brownout.dwell_ticks = 99                  # hold level 1
    eng.brownout.recover_ticks = 99
    eng.deploy(0.0)
    eng.queue.append(Request(rid=7, arrival=0.0, prompt_len=8, max_new=12))
    eng.tick(0.0, 1.0, lam=0.0)
    (rt,) = eng.runtimes.values()
    assert not rt.spec_enabled                     # luxury off while degraded
    log = list(rt.token_log[7])
    # prefill's first token + the capped 3 decode steps — not dropped
    assert len(log) == 4 < len(o_log)
    assert log == o_log[:len(log)]                 # prefix — same content
    assert not eng.shed


def test_breaker_routes_around_partitioned_replica(serving):
    """A replica that takes work but emits nothing is ejected and probed
    back in through the engine loop."""
    br = qos.ReplicaBreaker(stall_ticks=1, probe_after_s=5.0)
    br._state["r0"] = qos.BREAKER_OPEN             # ejected upstream
    br._opened_at["r0"] = 0.0
    eng = mk_engine(serving, service_rate=50.0)
    eng.breaker = br
    eng.deploy(0.0)
    (name,) = eng.runtimes.keys()
    br.forget("r0")
    br._state[name] = qos.BREAKER_OPEN
    br._opened_at[name] = 0.0
    eng.queue.append(Request(rid=5, arrival=0.0, prompt_len=8, max_new=2))
    eng.tick(1.0, 1.0, lam=0.0)
    assert not eng.completed                       # open: routed around
    assert len(eng.queue) == 1
    eng.tick(6.0, 1.0, lam=0.0)                    # half-open probe window
    assert [rid for rid, _ in eng.completed] == [5]
    assert br.state(name) == qos.BREAKER_CLOSED and br.rejoins == 1


# ------------------------------------------------- cost-modeled failover

def test_transfer_cost_model_and_parse():
    topo = SiteTopology.parse("jlab:nersc:40", "", "jlab:nersc:0.001")
    assert topo.bandwidth("jlab", "jlab") == float("inf")
    assert topo.bandwidth("jlab", "nersc") == 0.001
    assert topo.bandwidth("nersc", "jlab") == 0.001    # symmetric
    assert topo.transfer_cost(10 ** 6, "jlab", "jlab") == 0.0
    assert topo.transfer_cost(0, "jlab", "nersc") == 0.0
    # 1 MB over 1 Mbit/s = 8 s, plus the 40 ms one-way latency
    assert topo.transfer_cost(10 ** 6, "jlab", "nersc") == \
        pytest.approx(0.04 + 8.0)
    # unknown pairs fall back to the default pipe
    topo.set_bandwidth("jlab", "ornl", 2.0)
    assert topo.bandwidth("ornl", "jlab") == 2.0
    assert topo.bandwidth("nersc", "ornl") == topo.default_bandwidth_gbps


def test_preemption_ranks_cheap_transfers_first():
    cluster = Cluster()
    for name, site in (("a0", "jlab"), ("b0", "nersc")):
        cluster.register_node(
            start_vk(name, site=site, now=0.0,
                     slice_spec=SliceSpec(chips=2)), 0.0)
        cluster.heartbeat(name, 0.0)
    topo = SiteTopology.parse("jlab:nersc:40", "", "jlab:nersc:0.001")
    sched = Scheduler(cluster, topology=topo)
    from repro.core.state_machine import Container, Pod
    tol = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]
    rec = cluster.submit(Pod("v", [Container("c")], tolerations=tol,
                             request_chips=1), 0.0)
    rec.restored_state = {"kv": np.zeros(250_000, np.float32)}  # 1 MB
    assert sched._victim_state_bytes(rec) == 10 ** 6
    node = cluster.nodes["a0"]
    # the only other site is nersc over the thin pipe: ~8 s penalty
    assert sched._transfer_penalty([rec], node) == pytest.approx(0.04 + 8.0)
    # no topology -> no penalty term (legacy cost ordering preserved)
    assert Scheduler(cluster)._transfer_penalty([rec], node) == 0.0


def test_drain_site_pays_transfer_window_and_degrades(serving, tmp_path):
    cluster = Cluster()
    cluster.register_node(
        start_vk("j0", nodetype="tpu", site="jlab", now=0.0,
                 slice_spec=SliceSpec(chips=4)), 0.0)
    cluster.heartbeat("j0", 0.0)
    topo = SiteTopology.parse("jlab:nersc:40", "", "jlab:nersc:1e-09")
    from repro.core.controllers import ControlPlane
    plane = ControlPlane(cluster, scheduler=Scheduler(cluster,
                                                      topology=topo))
    plane.nodes.ckpt_dir = str(tmp_path)
    eng = StreamEngine(serving.cfg, serving, list(cluster.nodes.values()),
                       service_rate=50.0, cluster=cluster, plane=plane)
    eng.deploy(0.0)
    cluster.scale("ersap", 1, 0.0, source="test")
    eng.reconcile(0.0)
    assert all(cluster.nodes[p.node].site == "jlab"
               for p in eng.pods.values())
    cluster.register_node(
        start_vk("c0", nodetype="tpu", site="nersc", now=0.0,
                 slice_spec=SliceSpec(chips=4)), 0.0)
    cluster.heartbeat("c0", 0.0)
    now = 10.0
    plane.drain_site("jlab", now)
    assert plane.last_transfer_s > 0 and plane.last_transfer_bytes > 0
    assert any(e.reason == "SiteDrainTransfer" for e in cluster.events)
    # the engine was told to serve degraded for the transfer window
    assert eng.degrade_until == pytest.approx(now + plane.last_transfer_s)
    assert eng.transfer_windows == 1
    eng.reconcile(now)
    assert sorted(cluster.nodes[p.node].site
                  for p in eng.pods.values()) == ["nersc"]
    # while the window is open the tick runs at the forced degrade level
    eng.tick(now, 1.0, lam=0.0)
    assert eng._level >= eng.transfer_degrade_level
