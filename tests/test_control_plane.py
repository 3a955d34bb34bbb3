"""Declarative control plane: object store, reconciling scheduler,
drain-aware controllers, and the end-to-end churn scenario (§4.5.4 closed
loop: drain -> checkpoint -> evict -> reschedule with zero request loss)."""
import jax
import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.configs.base import get_config
from repro.core.cluster import (ADDED, DELETED, KIND_POD, Cluster,
                                Deployment, PodTemplate)
from repro.core.controllers import (ControlPlane, DeploymentController,
                                    NodeLifecycleController)
from repro.core.elastic import ElasticServing
from repro.core.jfm import FacilityManager
from repro.core.jrm import SliceSpec, start_vk
from repro.core.scheduler import Scheduler
from repro.core.state_machine import Container, Pod
from repro.models import model_api as MA
from repro.streaming.engine import StreamEngine

TOL = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]


def mkpod(name="p", chips=1, hbm=0):
    return Pod(name, [Container("c")], tolerations=list(TOL),
               request_chips=chips, request_hbm_bytes=hbm)


def mkcluster(n_nodes=3, chips=4, walltimes=None, now=0.0):
    cluster = Cluster()
    for i in range(n_nodes):
        wall = walltimes[i] if walltimes else 0.0
        cluster.register_node(
            start_vk(f"n{i}", walltime=wall, now=now,
                     slice_spec=SliceSpec(chips=chips)), now)
        cluster.heartbeat(f"n{i}", now)
    return cluster


# ----------------------------------------------------------- object store

def test_store_watch_bus_and_event_trail():
    cluster = mkcluster(1)
    seen = []
    cluster.watch(KIND_POD, lambda ev: seen.append((ev.type, ev.name)))
    cluster.submit(mkpod("a"), 1.0)
    Scheduler(cluster).run_once(1.0)
    cluster.evict("a", 2.0, reason="Evicted")
    assert (ADDED, "a") in seen and (DELETED, "a") in seen
    assert cluster.event_reasons("a") == ["Created", "Scheduled", "Evicted"]


def test_scale_is_a_spec_write_only():
    cluster = mkcluster(1)
    dep = cluster.apply_deployment(Deployment("d", 1), 0.0)
    cluster.scale("d", 3, 1.0, source="hpa")
    assert dep.replicas == 3
    assert not cluster.pods            # nothing created until a controller runs
    assert "Scaled" in cluster.event_reasons("d")


# -------------------------------------------------------------- scheduler

def test_scheduler_backoff_retries_until_capacity_frees():
    cluster = mkcluster(1, chips=2)
    sched = Scheduler(cluster, backoff_base=5.0, enable_preemption=False)
    cluster.submit(mkpod("big", chips=2), 0.0)
    sched.run_once(0.0)
    rec = cluster.submit(mkpod("waiting", chips=2), 0.0)
    sched.run_once(0.0)
    assert not rec.bound and rec.attempts == 1
    # exponential base stretched by the decorrelation jitter (<= 25%)
    assert 5.0 <= rec.next_retry <= 5.0 * (1 + sched.backoff_jitter)
    sched.run_once(1.0)                     # still backing off: not retried
    assert rec.attempts == 1
    sched.run_once(7.0)                     # retried, still no room
    assert rec.attempts == 2
    assert 10.0 <= rec.next_retry - 7.0 <= 10.0 * (1 + sched.backoff_jitter)
    cluster.evict("big", 20.0)              # capacity frees
    sched.run_once(20.0)
    assert rec.bound
    reasons = cluster.event_reasons("waiting")
    # both failed attempts share one reason -> one transition event
    assert reasons.count("FailedScheduling") == 1
    assert reasons[-1] == "Scheduled"


def test_scheduler_preemption_requeues_victims():
    cluster = mkcluster(1, chips=2)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("low", chips=2), 0.0, priority=0)
    sched.run_once(0.0)
    cluster.submit(mkpod("high", chips=2), 1.0, priority=10)
    decisions = sched.run_once(1.0)
    assert decisions[0].node == "n0" and decisions[0].preempted == ("low",)
    assert cluster.pods["high"].bound
    # victim was requeued, not lost
    assert "low" in cluster.pods and not cluster.pods["low"].bound
    assert "Preempted" in cluster.event_reasons("low")
    # second node appears -> the victim lands there on the next pass
    cluster.register_node(start_vk("n1", now=2.0,
                                   slice_spec=SliceSpec(chips=2)), 2.0)
    cluster.heartbeat("n1", 2.0)
    sched.run_once(2.0)
    assert cluster.pods["low"].pod.node == "n1"


def test_scheduler_never_preempts_onto_draining_node():
    cluster = mkcluster(1, chips=2, walltimes=[100.0])
    sched = Scheduler(cluster)
    cluster.submit(mkpod("low", chips=2), 0.0, priority=0)
    sched.run_once(0.0)
    now = 50.0                              # inside the 60s drain margin
    cluster.heartbeat("n0", now)
    cluster.submit(mkpod("high", chips=2), now, priority=10)
    decisions = sched.run_once(now)
    assert decisions[-1].node is None       # backoff, not preemption
    assert "low" in cluster.pods and cluster.pods["low"].bound


def test_scheduler_spreads_replicas_across_nodes():
    cluster = mkcluster(3, chips=4)
    sched = Scheduler(cluster)
    for i in range(3):
        cluster.submit(mkpod(f"r{i}", chips=1), 0.0)
    sched.run_once(0.0)
    nodes = {cluster.pods[f"r{i}"].pod.node for i in range(3)}
    assert nodes == {"n0", "n1", "n2"}


# ------------------------------------------------------------- controllers

def test_deployment_controller_converges_and_scales_down():
    cluster = mkcluster(2, chips=4)
    cluster.apply_deployment(Deployment("web", 3, template=PodTemplate(
        tolerations=list(TOL), request_chips=1)), 0.0)
    plane = ControlPlane(cluster)
    plane.step(0.0)
    assert len([r for r in cluster.pods.values() if r.bound]) == 3
    cluster.scale("web", 1, 5.0, source="user")
    plane.step(5.0)
    live = cluster.pods_of("web")
    assert len(live) == 1 and live[0].bound
    assert "ScaledDown" in cluster.event_reasons()


def test_node_failure_evicts_and_replaces():
    """Crash path: heartbeats stop, JFM feed marks the node NotReady, the
    lifecycle controller evicts, the deployment replaces, the scheduler
    re-places — all declaratively."""
    cluster = mkcluster(2, chips=4)
    fm = FacilityManager(stale_after=30.0)
    cluster.apply_deployment(Deployment("web", 2, template=PodTemplate(
        tolerations=list(TOL), request_chips=1)), 0.0)
    plane = ControlPlane(cluster)
    fm.feed(cluster, 0.0)
    plane.step(0.0)
    victim_node = cluster.pods_of("web")[0].pod.node
    survivor = next(n for n in cluster.nodes if n != victim_node)
    # only the survivor heartbeats from now on
    cluster.heartbeat(survivor, 100.0)
    fm.feed(cluster, 100.0)
    plane.step(100.0)
    live = [r for r in cluster.pods_of("web") if r.bound]
    assert len(live) == 2
    assert all(r.pod.node == survivor for r in live)


def test_drain_checkpoint_evict_reschedule_restores_state(tmp_path):
    """Satellite: a pod on a node whose lease enters the drain margin is
    checkpointed through repro.checkpoint, evicted, and rescheduled onto a
    healthy node with its runtime state restored."""
    counters = {}

    cluster = mkcluster(2, chips=4, walltimes=[120.0, 0.0])
    cluster.apply_deployment(Deployment("svc", 1, template=PodTemplate(
        tolerations=list(TOL), request_chips=1,
        checkpoint_state=lambda name: counters.get(name))), 0.0)
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    # force initial placement onto the short-lease node
    plane.scheduler.scorers = [
        lambda rec, node, sched, now: 1.0 if node.name == "n0" else 0.0]
    plane.step(0.0)
    first = cluster.pods_of("svc")[0]
    assert first.pod.node == "n0"
    counters[first.name] = {"served": 42, "tokens": 678}

    now = 70.0                              # alive_left = 50 < 60s margin
    for name in cluster.nodes:
        cluster.heartbeat(name, now)
    plane.scheduler.scorers = []            # back to neutral scoring
    plane.step(now)

    moved = cluster.pods_of("svc")[0]
    assert moved.name != first.name
    assert moved.pod.node == "n1" and moved.bound
    assert moved.restored_from == first.name
    assert int(moved.restored_state["served"]) == 42
    assert int(moved.restored_state["tokens"]) == 678
    # the checkpoint went through repro.checkpoint's atomic on-disk path
    assert checkpointer.latest_step(tmp_path / first.name) == 0
    # event trail: the §4.5.4 loop is auditable
    assert "Draining" in cluster.event_reasons("n0")
    old = cluster.event_reasons(first.name)
    assert "Checkpointed" in old and "Evicted" in old
    assert "Rescheduled" in cluster.event_reasons(moved.name)


# -------------------------------------------------- engine + control plane

def _engine(nodes_walltimes, service_rate=4.0, replicas=1, chips=4):
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(replicas, seed=0)
    nodes = [start_vk(f"n{i}", walltime=w, now=0.0,
                      slice_spec=SliceSpec(chips=chips))
             for i, w in enumerate(nodes_walltimes)]
    eng = StreamEngine(cfg, serving, nodes, service_rate=service_rate,
                       max_batch=4)
    return eng


def test_engine_scale_down_leaves_no_stale_stats_or_endpoints(tmp_path):
    """Satellite: retired replicas disappear from stats AND from the
    Service endpoints, so Prometheus stops scraping dead pods."""
    eng = _engine([0.0, 0.0], replicas=1)
    eng.deploy(0.0)
    eng.cluster.scale("ersap", 2, 1.0, source="test")
    eng.reconcile(1.0)
    assert len(eng.pods) == 2
    assert set(eng.stats) == set(eng.pods)
    eng.tick(2.0, 2.0, lam=2.0)
    served_before = eng.total_served
    eng.cluster.scale("ersap", 1, 3.0, source="test")
    eng.reconcile(3.0)
    live = set(eng.pods)
    assert len(live) == 1
    assert set(eng.stats) == live
    assert set(eng.registries) == live
    eps = {ep.pod for svc in eng.prom.services for ep in svc.endpoints}
    assert eps == live                      # no stale scrape targets
    assert eng.total_served == served_before   # global counters survive


def test_e2e_churn_zero_request_loss(tmp_path):
    """Acceptance: a streaming Deployment across 3 nodes; one node's
    walltime expires mid-run; the NodeLifecycleController checkpoints and
    evicts, the scheduler re-places the replica, and every in-flight
    request is eventually served — with the full event trail recorded."""
    eng = _engine([160.0, 0.0, 0.0], service_rate=6.0, chips=2)
    eng.deploy(0.0)
    eng.plane.nodes.ckpt_dir = str(tmp_path / "drain")
    # single-CPU jax clamps the mesh to 1 data replica; the Deployment
    # spec is still free to ask for 2 simulated serving pods
    eng.cluster.scale("ersap", 2, 0.0, source="test")
    eng.reconcile(0.0)
    assert len(eng.pods) == 2
    # one replica sits on the doomed short-lease node (spread scoring
    # guarantees the two replicas land on distinct nodes)
    assert len({p.node for p in eng.pods.values()}) == 2

    dt = 10.0
    for t in range(16):
        now = t * dt
        for name in eng.cluster.nodes:
            eng.cluster.heartbeat(name, now)
        eng.reconcile(now)
        eng.tick(now, dt, lam=1.0 if t < 10 else 0.0)
    # drain ticks: no new arrivals, queue must empty through live replicas
    for t in range(16, 22):
        now = t * dt
        for name in eng.cluster.nodes:
            eng.cluster.heartbeat(name, now)
        eng.reconcile(now)
        eng.tick(now, dt, lam=0.0)

    # zero lost in-flight requests: everything that arrived completed
    assert eng.source.rid > 0
    assert len(eng.completed) == eng.source.rid
    assert len(eng.queue) == 0
    # the replica set converged back to spec on healthy nodes
    assert len(eng.pods) == 2
    assert all(p.node != "n0" for p in eng.pods.values())
    # event trail: Scheduled -> Draining -> (Checkpointed) -> Evicted ->
    # Rescheduled all visible in the Cluster event store
    reasons = eng.cluster.event_reasons()
    for expected in ("Scheduled", "Draining", "Checkpointed", "Evicted",
                     "Rescheduled"):
        assert expected in reasons, f"missing {expected} in {set(reasons)}"
    # the moved replica carried its counters across the reschedule
    moved = [r for r in eng.cluster.pods_of("ersap") if r.restored_from]
    assert moved and moved[0].restored_state is not None


def test_elastic_tp_beyond_devices_raises():
    """A tensor-parallel degree larger than the platform's device count is
    an error at once, not a one-replica clamp that fails later in
    make_mesh."""
    cfg = get_config("qwen2-7b").reduced()
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"tp={n + 1} needs"):
        ElasticServing(cfg, tp=n + 1).max_replicas()
    assert ElasticServing(cfg, tp=1).max_replicas() == n


def test_elastic_seeded_build_draws_params_on_device():
    """``build(seed=...)`` draws the parameters inside one jitted init,
    sharded as param_axes says: the same values as an eager init from the
    same key, already committed to the serving mesh."""
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    eager = MA.get_module(cfg).init(jax.random.PRNGKey(0), cfg)
    for got, want in zip(jax.tree.leaves(serving.params),
                         jax.tree.leaves(eager)):
        assert got.sharding.mesh is serving.mesh
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    with pytest.raises(RuntimeError, match="no params yet"):
        ElasticServing(cfg, tp=1).build(1)
