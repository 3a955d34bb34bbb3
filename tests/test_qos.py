"""QoS subsystem: priority classes, fair-share quotas, cost-ranked
preemption with §4.5.4 checkpointing, the pressure-aware autoscaler, and
the twin's (replicas, priority) action space.

Invariants under test: preemption never selects equal-or-higher priority
or non-preemptible pods; quota books balance (used + free == capacity,
per-owner sums match the node truth) after preempt -> requeue ->
reschedule; priority writes round-trip through a full drain; a
mixed-tenant pressure spike loses zero serving requests."""
import pytest

from repro.configs.base import get_config
from repro.core import qos
from repro.core.cluster import Cluster, Deployment, PodTemplate
from repro.core.controllers import ControlPlane
from repro.core.digital_twin.control import ControlPolicy
from repro.core.digital_twin.dbn import DigitalTwin
from repro.core.elastic import ElasticServing
from repro.core.hpa import HPA, HPAConfig, PressureSignals
from repro.core.jrm import SliceSpec, start_vk
from repro.core.scheduler import Scheduler
from repro.core.state_machine import Container, Pod
from repro.streaming.engine import StreamEngine

TOL = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]


def mkpod(name="p", chips=1, hbm=0):
    return Pod(name, [Container("c")], tolerations=list(TOL),
               request_chips=chips, request_hbm_bytes=hbm)


def mkcluster(n_nodes=2, chips=2, sites=None, walltimes=None, now=0.0):
    cluster = Cluster()
    for i in range(n_nodes):
        cluster.register_node(
            start_vk(f"n{i}", site=sites[i] if sites else "Local",
                     walltime=walltimes[i] if walltimes else 0.0, now=now,
                     slice_spec=SliceSpec(chips=chips)), now)
        cluster.heartbeat(f"n{i}", now)
    return cluster


# ---------------------------------------------------------- object model

def test_priority_class_resolves_at_submit():
    cluster = mkcluster(1)
    rec = cluster.submit(mkpod("a"), 0.0, priority_class="latency-critical")
    assert rec.priority == 100 and rec.preemptible
    sys_rec = cluster.submit(mkpod("b"), 0.0, priority_class="system")
    assert sys_rec.priority == 1000 and not sys_rec.preemptible
    with pytest.raises(ValueError):
        cluster.submit(mkpod("c"), 0.0, priority_class="no-such-tier")


def test_set_priority_retiers_live_and_pending_pods():
    cluster = mkcluster(1, chips=1)
    cluster.apply_deployment(Deployment("svc", 2, template=PodTemplate(
        tolerations=list(TOL), request_chips=1,
        priority_class="standard")), 0.0)
    plane = ControlPlane(cluster)
    plane.step(0.0)
    recs = cluster.pods_of("svc")
    bound = [r for r in recs if r.bound]
    pending = [r for r in recs if not r.bound]
    assert len(bound) == 1 and len(pending) == 1
    assert pending[0].next_retry > 0.0            # backed off
    cluster.set_priority("svc", "latency-critical", 5.0, source="twin")
    for r in cluster.pods_of("svc"):
        assert r.priority == 100
        assert r.priority_class == "latency-critical"
    # an escalated pending pod re-enters scheduling immediately
    assert pending[0].next_retry == 5.0 and pending[0].attempts == 0
    assert "PriorityChanged" in cluster.event_reasons("svc")
    # idempotent: no second event for the same tier
    cluster.set_priority("svc", "latency-critical", 6.0)
    assert cluster.event_reasons("svc").count("PriorityChanged") == 1
    # a *demotion* must not void the pending pod's backoff (only raises
    # re-enter scheduling; apply_deployment synced template.priority to
    # the class, so the raise-vs-demote comparison is against the real
    # tier, not the dataclass default 0)
    pending[0].next_retry = 99.0
    pending[0].attempts = 3
    cluster.set_priority("svc", "batch", 7.0)
    assert pending[0].next_retry == 99.0 and pending[0].attempts == 3
    # apply_deployment resolves a class-created template's numeric mirror
    dep2 = cluster.apply_deployment(Deployment("svc2", 1, template=PodTemplate(
        tolerations=list(TOL), priority_class="latency-critical")), 8.0)
    assert dep2.template.priority == 100


def test_quota_spec_parser():
    quotas = qos.parse_quotas("ersap:chips=8:kv_pages=1024,batch@jlab:chips=4")
    assert quotas[0] == qos.Quota("ersap", None, 8, None, 1024)
    assert quotas[1] == qos.Quota("batch", "jlab", 4, None, None)
    with pytest.raises(ValueError):
        qos.parse_quotas("ersap:watts=9")
    with pytest.raises(ValueError):
        qos.parse_quotas("ersap")


# --------------------------------------------------------------- quotas

def test_quota_filter_blocks_and_releases():
    cluster = mkcluster(2, chips=4)
    cluster.apply_quota(qos.Quota(owner="team", chips=2), 0.0)
    sched = Scheduler(cluster)
    for i in range(3):
        cluster.submit(mkpod(f"t{i}", chips=1), 0.0, owner="team")
    sched.run_once(0.0)
    bound = [r for r in cluster.pods.values() if r.bound]
    assert len(bound) == 2                       # third is over quota
    blocked = cluster.pods[next(r.name for r in cluster.pods.values()
                                if not r.bound)]
    assert "quota" in blocked.last_reason
    # quota-blocked pods park at max backoff and log one transition event
    assert blocked.next_retry == sched.backoff_max
    sched.run_once(sched.backoff_max + 1.0)
    assert cluster.event_reasons(blocked.name).count("FailedScheduling") == 1
    # a scale-down frees fair share -> the blocked pod binds
    cluster.evict(bound[0].name, 200.0)
    sched.run_once(200.0)
    assert cluster.pods[blocked.name].bound
    cluster.ledger.assert_balanced()


def test_failed_scheduling_event_reemitted_on_reason_transition():
    cluster = mkcluster(1, chips=1)
    cluster.apply_quota(qos.Quota(owner="team", chips=0), 0.0)
    sched = Scheduler(cluster)
    rec = cluster.submit(mkpod("a"), 0.0, owner="team")
    sched.run_once(0.0)
    sched.run_once(sched.backoff_max + 1.0)      # same reason: no new event
    assert cluster.event_reasons("a").count("FailedScheduling") == 1
    # an unquota'd pod takes the chip while "a" is parked...
    cluster.submit(mkpod("hog", chips=1), 70.0)
    sched.run_once(70.0)
    assert cluster.pods["hog"].bound
    # ...then the quota is raised: capacity is the blocker now — a
    # different reason, so exactly one more transition event
    cluster.apply_quota(qos.Quota(owner="team", chips=4), 130.0)
    sched.run_once(float(2 * sched.backoff_max + 71.0))
    assert not cluster.pods["a"].bound
    assert "chips" in rec.last_reason
    assert cluster.event_reasons("a").count("FailedScheduling") == 2


def test_per_site_quota_steers_to_other_site():
    cluster = mkcluster(2, chips=2, sites=["jlab", "nersc"])
    cluster.apply_quota(qos.Quota(owner="team", site="jlab", chips=0), 0.0)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("a"), 0.0, owner="team")
    sched.run_once(0.0)
    rec = cluster.pods["a"]
    assert rec.bound and cluster.nodes[rec.pod.node].site == "nersc"


def test_kv_pages_quota_counts_declared_pools():
    cluster = mkcluster(2, chips=4)
    cluster.apply_quota(qos.Quota(owner="serve", kv_pages=100), 0.0)
    sched = Scheduler(cluster)
    a = cluster.submit(mkpod("a"), 0.0, owner="serve", request_kv_pages=64)
    sched.run_once(0.0)
    assert a.bound
    b = cluster.submit(mkpod("b"), 1.0, owner="serve", request_kv_pages=64)
    sched.run_once(1.0)
    assert not b.bound and "kv_pages" in b.last_reason
    assert cluster.ledger.usage("serve").kv_pages == 64


def test_fair_share_orders_equal_priority_queue():
    cluster = mkcluster(1, chips=4)
    cluster.apply_quota(qos.Quota(owner="hog", chips=4), 0.0)
    cluster.apply_quota(qos.Quota(owner="fair", chips=4), 0.0)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("h0", chips=3), 0.0, owner="hog")
    sched.run_once(0.0)                          # hog at 3/4 share
    # one chip left; hog submitted FIRST but fair is further below quota
    cluster.submit(mkpod("h1", chips=1), 1.0, owner="hog")
    cluster.submit(mkpod("f0", chips=1), 2.0, owner="fair")
    sched.run_once(3.0)
    assert cluster.pods["f0"].bound
    assert not cluster.pods["h1"].bound


def test_reject_classification_ignores_node_and_owner_names():
    """Reject kinds are classified on the reason after the "node: "
    prefix — a node named 'quota-exp-0' must not make a capacity reject
    read as quota-blocked (which would park the pod at max backoff and
    hide it from reprovision's starved-chips sizing)."""
    from repro.core.jcs import CentralService
    cluster = Cluster()
    cluster.register_node(start_vk("quota-exp-0", now=0.0,
                                   slice_spec=SliceSpec(chips=1)), 0.0)
    cluster.heartbeat("quota-exp-0", 0.0)
    sched = Scheduler(cluster)
    rec = cluster.submit(mkpod("big", chips=2), 0.0)
    sched.run_once(0.0)
    assert "insufficient chips" in rec.last_reason
    # exponential backoff (capacity can free), not the quota park
    assert sched.backoff_base <= rec.next_retry \
        <= sched.backoff_base * (1 + sched.backoff_jitter)
    assert rec.next_retry < sched.backoff_max
    # and reprovision still counts it as chip-starved
    assert CentralService._starved_chips(cluster, 1.0) == {"Local": [2]}


# ----------------------------------------------------------- preemption

def test_preemption_never_selects_equal_or_higher_priority():
    cluster = mkcluster(1, chips=2)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("peer", chips=2), 0.0, priority_class="standard")
    sched.run_once(0.0)
    cluster.submit(mkpod("claimant", chips=2), 1.0,
                   priority_class="standard")
    sched.run_once(1.0)
    # equal priority: no preemption, the claimant backs off
    assert cluster.pods["peer"].bound
    assert not cluster.pods["claimant"].bound
    assert "Preempted" not in cluster.event_reasons()
    # escalate the claimant -> strictly higher now, preemption fires
    rec = cluster.pods["claimant"]
    rec.priority, rec.priority_class = 100, "latency-critical"
    rec.next_retry = 2.0
    sched.run_once(2.0)
    assert cluster.pods["claimant"].bound
    assert "Preempted" in cluster.event_reasons("peer")
    assert "peer" in cluster.pods and not cluster.pods["peer"].bound


def test_preemption_skips_non_preemptible_victims():
    cluster = mkcluster(1, chips=2)
    # a low-priority but non-preemptible tier (e.g. a licensed daemon)
    cluster.apply_priority_class(
        qos.PriorityClass("pinned", 1, preemptible=False), 0.0)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("pin", chips=2), 0.0, priority_class="pinned")
    sched.run_once(0.0)
    cluster.submit(mkpod("hi", chips=2), 1.0,
                   priority_class="latency-critical")
    sched.run_once(1.0)
    assert cluster.pods["pin"].bound             # untouched
    assert not cluster.pods["hi"].bound
    assert "Preempted" not in cluster.event_reasons()


def test_preempt_checkpoints_victim_and_books_balance(tmp_path):
    """Victims take the §4.5.4 checkpoint path: the requeued record
    carries the snapshot, the rebind is a Rescheduled event, and the
    quota ledger balances at every step of preempt -> requeue ->
    reschedule."""
    state = {"batch-0": {"step": 41}}
    cluster = mkcluster(1, chips=2)
    cluster.apply_quota(qos.Quota(owner="batch", chips=2), 0.0)
    cluster.apply_deployment(Deployment("batch", 1, template=PodTemplate(
        tolerations=list(TOL), request_chips=2, priority_class="batch",
        checkpoint_state=lambda name: state.get(name))), 0.0)
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    plane.step(0.0)
    assert cluster.pods["batch-0"].bound
    cluster.ledger.assert_balanced()

    cluster.submit(mkpod("hot", chips=2), 10.0,
                   priority_class="latency-critical")
    plane.scheduler.run_once(10.0)
    assert cluster.pods["hot"].bound
    victim = cluster.pods["batch-0"]
    assert not victim.bound
    assert victim.restored_from == "batch-0"
    assert int(victim.restored_state["step"]) == 41
    assert victim.priority_class == "batch"      # spec intact
    assert "Checkpointed" in cluster.event_reasons("batch-0")
    cluster.ledger.assert_balanced()

    # capacity appears -> the victim reschedules with its state
    cluster.register_node(start_vk("n1", now=20.0,
                                   slice_spec=SliceSpec(chips=2)), 20.0)
    cluster.heartbeat("n1", 20.0)
    plane.scheduler.run_once(20.0)
    moved = cluster.pods["batch-0"]
    assert moved.bound and moved.pod.node == "n1"
    assert "Rescheduled" in cluster.event_reasons("batch-0")
    books = cluster.ledger.assert_balanced()
    assert books["chips_used"] == 4


def test_preemptor_cannot_bypass_own_quota():
    cluster = mkcluster(1, chips=2)
    cluster.apply_quota(qos.Quota(owner="hot", chips=0), 0.0)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("low", chips=2), 0.0, priority_class="batch")
    sched.run_once(0.0)
    cluster.submit(mkpod("h0", chips=2), 1.0, owner="hot",
                   priority_class="latency-critical")
    sched.run_once(1.0)
    assert cluster.pods["low"].bound             # quota blocks the preemptor
    assert not cluster.pods["h0"].bound
    assert "Preempted" not in cluster.event_reasons()


# ------------------------------------------------- autoscaler + policy

def test_hpa_multi_signal_takes_max_proposal():
    cfg = HPAConfig(target=10.0, max_replicas=8, tokens_target=100.0,
                    occupancy_target=0.8, scale_down_stabilization=0.0)
    hpa = HPA(cfg)
    # queue calm, tokens calm, but the slab is saturated -> scale on memory
    d = hpa.evaluate_signals(2, PressureSignals(
        queue_depth=20.0, tokens_per_s=200.0, slab_occupancy=1.0), 0.0)
    assert d == 3                                 # ceil(2 * 1.0 / 0.8)
    # all signals in-band: hold
    hpa2 = HPA(cfg)
    assert hpa2.evaluate_signals(2, PressureSignals(
        queue_depth=20.0, tokens_per_s=200.0, slab_occupancy=0.8), 0.0) == 2
    # queue pressure dominates when it proposes more
    hpa3 = HPA(cfg)
    assert hpa3.evaluate_signals(2, PressureSignals(
        queue_depth=80.0, tokens_per_s=0.0, slab_occupancy=0.0), 0.0) == 8


def test_hpa_signals_respect_stabilization_window():
    cfg = HPAConfig(target=10.0, max_replicas=8,
                    scale_down_stabilization=300.0)
    hpa = HPA(cfg)
    assert hpa.evaluate_signals(2, PressureSignals(queue_depth=80.0),
                                0.0) == 8
    # pressure gone, but the 8-recommendation is inside the window
    assert hpa.evaluate_signals(8, PressureSignals(queue_depth=0.0),
                                100.0) == 8
    assert hpa.evaluate_signals(8, PressureSignals(queue_depth=0.0),
                                400.0) < 8


def test_policy_action_space_and_hysteresis():
    policy = ControlPolicy(occupancy_high=0.9, occupancy_low=0.5)
    twin = DigitalTwin()
    # calm queue, calm slab: low tier
    for _ in range(4):
        twin.assimilate(5.0, 16)
    control, tier = policy.recommend_action(twin, 16, 0.0, occupancy=0.2)
    assert control == 16 and tier == "standard"
    # memory pressure alone escalates the tier at unchanged capacity
    control, tier = policy.recommend_action(twin, 16, 1.0, occupancy=0.95)
    assert control == 16 and tier == "latency-critical"
    # hysteresis band: mid occupancy keeps the previous tier
    control, tier = policy.recommend_action(twin, 16, 2.0, occupancy=0.7)
    assert tier == "latency-critical"
    # clear the band: back to standard
    control, tier = policy.recommend_action(twin, 16, 3.0, occupancy=0.1)
    assert tier == "standard"
    # predicted queue spike escalates capacity AND tier together
    for _ in range(6):
        twin.assimilate(240.0, 16)
    control, tier = policy.recommend_action(twin, 16, 4.0, occupancy=0.1)
    assert control == 32 and tier == "latency-critical"


# ------------------------------------------------------ drain round-trip

def test_priority_write_round_trips_full_drain(tmp_path):
    """The twin's priority write survives the §4.5.4 loop: after a full
    walltime drain the replacement pods (new names, restored state) come
    back at the escalated tier."""
    counters = {}
    cluster = mkcluster(2, chips=2, walltimes=[120.0, 0.0])
    cluster.apply_deployment(Deployment("svc", 1, template=PodTemplate(
        tolerations=list(TOL), request_chips=1, priority_class="standard",
        checkpoint_state=lambda name: counters.get(name))), 0.0)
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    plane.scheduler.scorers = [
        lambda rec, node, sched, now: 1.0 if node.name == "n0" else 0.0]
    plane.step(0.0)
    first = cluster.pods_of("svc")[0]
    assert first.pod.node == "n0" and first.priority == 10
    counters[first.name] = {"served": 7}
    cluster.set_priority("svc", "latency-critical", 30.0, source="twin")
    assert cluster.pods_of("svc")[0].priority == 100

    now = 70.0                                   # inside the drain margin
    for name in cluster.nodes:
        cluster.heartbeat(name, now)
    plane.scheduler.scorers = []
    plane.step(now)
    moved = cluster.pods_of("svc")[0]
    assert moved.name != first.name and moved.bound
    assert moved.restored_from == first.name
    assert int(moved.restored_state["served"]) == 7
    # the escalated tier survived the drain into the replacement's spec
    assert moved.priority_class == "latency-critical"
    assert moved.priority == 100


# -------------------------------------------------- mixed-tenant e2e

def test_mixed_tenant_spike_zero_serving_loss(tmp_path):
    """Acceptance (compact bench_priority_spike): serving + saturating
    batch tenant at equal priority; a priority write + scale-up preempts
    batch (checkpointed), de-escalation lets batch resume — and every
    serving request that arrived is served exactly once."""
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    cluster = Cluster()
    for i in range(2):
        cluster.register_node(start_vk(f"n{i}", now=0.0,
                                       slice_spec=SliceSpec(chips=2)), 0.0)
        cluster.heartbeat(f"n{i}", 0.0)
    cluster.apply_quota(qos.Quota(owner="ersap", chips=2), 0.0)
    cluster.apply_quota(qos.Quota(owner="batch", chips=3), 0.0)
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    eng = StreamEngine(cfg, serving, list(cluster.nodes.values()),
                       service_rate=6.0, max_batch=4,
                       cluster=cluster, plane=plane)
    eng.deploy(0.0)

    batch = qos.BatchTenant(cluster, 3, priority_class="standard")
    eng.reconcile(0.0)
    assert batch.bound == 3

    dt = 10.0
    for t in range(18):
        now = t * dt
        for name in cluster.nodes:
            cluster.heartbeat(name, now)
        if t == 4:      # spike: the control writes (priority, replicas)
            cluster.set_priority("ersap", "latency-critical", now,
                                 source="twin")
            cluster.scale("ersap", 2, now, source="twin")
        if t == 10:     # spike over
            cluster.set_priority("ersap", "standard", now, source="twin")
            cluster.scale("ersap", 1, now, source="twin")
        eng.reconcile(now)
        batch.advance()
        eng.tick(now, dt, lam=1.5 if t < 12 else 0.0)
        cluster.ledger.assert_balanced()
        if t == 2:
            # the slab gauge scrapes the per-tick peak, not the post-pump
            # quiescent value (which is always 0)
            assert any(
                reg.metrics["ersap_slab_slots_used"].value > 0
                for reg in eng.registries.values()
                if "ersap_slab_slots_used" in reg.metrics)
    # a batch pod was preempted and resumed with checkpoint-identical state
    assert batch.resumed and not batch.mismatches
    assert eng.source.rid > 0
    assert len(eng.completed) == eng.source.rid   # zero loss, exactly once
    assert len(eng.queue) == 0
    preempted = [ev.name for ev in cluster.events
                 if ev.reason == "Preempted"]
    assert preempted and all(n.startswith("batch") for n in preempted)
