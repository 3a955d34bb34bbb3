"""Chaos fault-injection subsystem + failure-hardened recovery paths.

Covers the ISSUE-7 tentpole end to end: deterministic seeded fault
schedules through control-plane seams (``repro.core.chaos``), checkpoint
integrity manifests with fall-back to the last good generation, bounded
retry-with-backoff on flaky I/O, epoch fencing of partitioned nodes,
two-phase drains that survive a mid-drain walltime cut, the flap window
(NotReady with fresh heartbeats is NOT an eviction), and the every-tick
``InvariantAuditor``. The capstone scenario partitions a serving node
mid-run and proves the re-served work is token-identical (prefix replay)
to a fault-free oracle with zero request loss and exactly-once
completion.
"""
import json

import numpy as np
import pytest

from repro.checkpoint import checkpointer
from repro.configs.base import get_config
from repro.core.chaos import (ChaosInvariantError, FaultInjector, FaultSpec,
                              InvariantAuditor, corrupt_latest_generation)
from repro.core.cluster import Cluster, Deployment, PodTemplate
from repro.core.controllers import ControlPlane
from repro.core.elastic import ElasticServing
from repro.core.jrm import SliceSpec, start_vk
from repro.core.scheduler import Scheduler, _jitter_u
from repro.core.state_machine import Container, Pod
from repro.streaming.engine import StreamEngine
from repro.streaming.runtime import RuntimeConfig

TOL = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]


def mkpod(name="p", chips=1):
    return Pod(name, [Container("c")], tolerations=list(TOL),
               request_chips=chips)


def mkcluster(n_nodes=3, chips=4, walltimes=None, now=0.0):
    cluster = Cluster()
    for i in range(n_nodes):
        wall = walltimes[i] if walltimes else 0.0
        cluster.register_node(
            start_vk(f"n{i}", walltime=wall, now=now,
                     slice_spec=SliceSpec(chips=chips)), now)
        cluster.heartbeat(f"n{i}", now)
    return cluster


# ------------------------------------------------------------ fault specs

def test_faultspec_parse_forms():
    s = FaultSpec.parse("partition:n0@120+45")
    assert (s.kind, s.target, s.at, s.duration) == \
        ("partition", "n0", 120.0, 45.0)
    s = FaultSpec.parse("straggler:*@60+30x8")
    assert (s.target, s.duration, s.magnitude) == ("*", 30.0, 8.0)
    s = FaultSpec.parse("walltime_cut:n2@100x70")
    assert s.magnitude == 70.0 and s.duration == 0.0
    assert FaultSpec.parse("crash@10").target == "*"   # bare kind
    with pytest.raises(ValueError):
        FaultSpec.parse("meteor:n0@5")                 # unknown kind
    with pytest.raises(ValueError):
        FaultSpec.parse("crash:n0")                    # missing @time


def test_injector_seeded_wildcard_is_deterministic():
    logs = []
    for _ in range(2):
        cluster = mkcluster(4)
        inj = FaultInjector(["crash:*@5", "flap:*@10+10"], seed=7)
        for t in (0.0, 5.0, 10.0, 15.0, 25.0):
            inj.apply(cluster, t)
        logs.append(list(inj.log))
        # the crashed node's heartbeat clock froze at the pre-crash tick
        victim = next(tgt for (_, kind, tgt) in inj.log if kind == "crash")
        assert cluster.nodes[victim].last_heartbeat == 0.0
    assert logs[0] == logs[1] and logs[0]


# ------------------------------------------------- checkpoint durability

def test_save_writes_integrity_manifest(tmp_path):
    tree = {"a": np.arange(6, dtype=np.int64),
            "b": np.ones((2, 3), np.float32)}
    checkpointer.save(tmp_path, 0, tree)
    meta = json.loads((tmp_path / "step_00000000" / "meta.json").read_text())
    assert len(meta["checksums"]) == 2
    assert meta["tree_keys"] == ["a", "b"]
    assert checkpointer.verify_step(tmp_path, 0)


def test_truncated_generation_falls_back_to_last_good(tmp_path):
    tree0 = {"served": np.asarray(7), "tokens": np.asarray(100)}
    tree1 = {"served": np.asarray(9), "tokens": np.asarray(140)}
    checkpointer.save(tmp_path, 0, tree0)
    checkpointer.save(tmp_path, 1, tree1)
    hit = corrupt_latest_generation(tmp_path)      # truncates on disk
    assert hit is not None and "step_00000001" in hit
    assert checkpointer.latest_step(tmp_path) == 1
    assert checkpointer.latest_good_step(tmp_path) == 0
    assert not checkpointer.verify_step(tmp_path, 1)
    # asking for the damaged generation explicitly is an integrity error
    with pytest.raises(checkpointer.CheckpointCorruptError):
        checkpointer.restore(tmp_path, tree1, step=1)
    # asking for "the latest" silently recovers from the last good one
    got, meta = checkpointer.restore(tmp_path, tree0)
    assert meta["step"] == 0 and int(got["served"]) == 7
    # crash path: rebuild from disk alone via the tree_keys manifest
    state, meta2 = checkpointer.load_tree(tmp_path)
    assert meta2["step"] == 0 and int(state["tokens"]) == 100


def test_bitflip_fails_leaf_checksum(tmp_path):
    checkpointer.save(tmp_path, 0, {"w": np.arange(32, dtype=np.int64)})
    npz = tmp_path / "step_00000000" / "leaves.npz"
    raw = bytearray(npz.read_bytes())
    raw[len(raw) // 2] ^= 0xFF                     # flip a payload byte
    npz.write_bytes(bytes(raw))
    assert not checkpointer.verify_step(tmp_path, 0)
    assert checkpointer.latest_good_step(tmp_path) is None
    with pytest.raises(FileNotFoundError):
        checkpointer.load_tree(tmp_path)           # no usable generation


def test_with_retry_bounded_backoff_and_timeout():
    calls = {"n": 0}
    naps = []

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("EIO")
        return "ok"

    assert checkpointer.with_retry(flaky, retries=3, backoff=0.01,
                                   sleep=naps.append) == "ok"
    assert calls["n"] == 3
    assert naps == pytest.approx([0.01, 0.02])     # exponential backoff

    def always():
        raise OSError("mount wedged")

    with pytest.raises(OSError):
        checkpointer.with_retry(always, retries=1, backoff=0.01,
                                sleep=naps.append)
    # a zero wall budget stops retrying even with attempts left
    calls["n"] = 0

    def count_and_fail():
        calls["n"] += 1
        raise OSError("down")

    with pytest.raises(OSError):
        checkpointer.with_retry(count_and_fail, retries=50, backoff=0.0,
                                timeout=0.0, sleep=lambda s: None)
    assert calls["n"] == 1


# ------------------------------------------------------- scheduler jitter

def test_backoff_jitter_is_deterministic_and_decorrelates():
    assert _jitter_u("a", 1) == _jitter_u("a", 1)
    assert _jitter_u("a", 1) != _jitter_u("b", 1)
    assert _jitter_u("a", 1) != _jitter_u("a", 2)
    for n in ("a", "b", "c"):
        assert 0.0 <= _jitter_u(n, 1) < 1.0


def test_scheduler_jitter_spreads_synchronized_retries():
    cluster = mkcluster(1, chips=1)
    sched = Scheduler(cluster, backoff_base=5.0, enable_preemption=False)
    cluster.submit(mkpod("hog", chips=1), 0.0)
    sched.run_once(0.0)
    ra = cluster.submit(mkpod("wa", chips=1), 0.0)
    rb = cluster.submit(mkpod("wb", chips=1), 0.0)
    sched.run_once(0.0)
    # same base backoff, same tick — the thundering herd is decorrelated
    assert ra.next_retry != rb.next_retry
    for rec in (ra, rb):
        assert 5.0 <= rec.next_retry <= 5.0 * (1 + sched.backoff_jitter)
    # jitter off: exact exponential base (the pre-PR behavior)
    cluster2 = mkcluster(1, chips=1)
    sched2 = Scheduler(cluster2, backoff_base=5.0, backoff_jitter=0.0,
                       enable_preemption=False)
    cluster2.submit(mkpod("hog", chips=1), 0.0)
    sched2.run_once(0.0)
    rc = cluster2.submit(mkpod("wc", chips=1), 0.0)
    sched2.run_once(0.0)
    assert rc.next_retry == pytest.approx(5.0)


# ----------------------------------------------------------- flap window

def test_flap_window_no_eviction_single_recovery_event():
    cluster = mkcluster(1)
    plane = ControlPlane(cluster)
    cluster.submit(mkpod("p"), 0.0)
    plane.step(0.0)
    assert cluster.pods["p"].bound
    inj = FaultInjector(["flap:n0@10+30"])
    for t in range(0, 80, 10):
        inj.apply(cluster, float(t))
        plane.step(float(t))
    # NotReady with fresh heartbeats is a flap, not a death: no eviction
    assert cluster.pods["p"].bound
    assert "Evicted" not in cluster.event_reasons("p")
    # one NotReady episode -> exactly one NodeRecovered event
    assert cluster.event_reasons("n0").count("NodeRecovered") == 1


def test_stale_heartbeats_still_fail_the_node():
    """The flap fix must not soften real deaths: a NotReady node whose
    heartbeats also went stale is failed and its pods re-served."""
    cluster = mkcluster(2)
    cluster.apply_deployment(Deployment("svc", 1, template=PodTemplate(
        tolerations=list(TOL), request_chips=1)), 0.0)
    plane = ControlPlane(cluster)
    plane.step(0.0)
    victim = cluster.pods_of("svc")[0].pod.node
    survivor = next(n for n in cluster.nodes if n != victim)
    inj = FaultInjector([FaultSpec("crash", 10.0, victim)])
    for t in range(0, 60, 10):
        inj.apply(cluster, float(t))
        plane.step(float(t))
    live = cluster.pods_of("svc")
    assert len(live) == 1 and live[0].pod.node == survivor


# ------------------------------------------------------ chaos filesystem

def test_injector_ckpt_corrupt_hits_disk_through_the_schedule(tmp_path):
    pod_dir = tmp_path / "svc-0"
    checkpointer.save(pod_dir, 0, {"served": np.asarray(5)})
    cluster = mkcluster(1)
    inj = FaultInjector([FaultSpec("ckpt_corrupt", 1.0, "svc-0")],
                        ckpt_dir=str(tmp_path))
    inj.apply(cluster, 1.0)
    assert not checkpointer.verify_step(pod_dir, 0)
    assert any(e.reason == "ChaosCkptCorrupt" for e in cluster.events)
    # the recovery path sees no usable generation -> {} (start fresh),
    # not a crash
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    assert plane.nodes.recover_from_disk("svc-0", 2.0) == {}


# --------------------------------------------------- two-phase drain

def test_walltime_cut_mid_drain_resumes_from_background_checkpoint(tmp_path):
    """Phase 1 (periodic background snapshots) + phase 2 (paced drain):
    a walltime cut interrupts the drain after one pod; the survivor is
    recovered from its last background generation — not start-fresh."""
    counters = {}
    cluster = mkcluster(2, chips=4, walltimes=[1000.0, 0.0])
    cluster.apply_deployment(Deployment("svc", 2, template=PodTemplate(
        tolerations=list(TOL), request_chips=1,
        checkpoint_state=lambda name: counters.get(name))), 0.0)
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    plane.nodes.bg_checkpoint_every = 10.0
    plane.nodes.drain_pods_per_tick = 1
    # both replicas start on the doomed node
    plane.scheduler.scorers = [
        lambda rec, node, sched, now: 1.0 if node.name == "n0" else 0.0]
    plane.step(0.0)
    first = sorted(r.name for r in cluster.pods_of("svc"))
    assert len(first) == 2
    assert all(r.pod.node == "n0" for r in cluster.pods_of("svc"))
    for i, name in enumerate(first):
        counters[name] = {"served": 10 + i, "tokens": 100 + i}
    plane.scheduler.scorers = []

    inj = FaultInjector(["walltime_cut:n0@30x10"])   # 10s of lease left
    for t in (10.0, 20.0, 30.0, 40.0, 50.0):
        inj.apply(cluster, t)
        plane.step(t)

    assert cluster.nodes["n0"].walltime == pytest.approx(40.0)
    live = cluster.pods_of("svc")
    assert len(live) == 2 and all(r.pod.node == "n1" for r in live)
    reasons = cluster.event_reasons()
    # one pod drained gracefully before the cut bit...
    assert "Checkpointed" in reasons
    # ...the other was caught mid-drain and recovered from the last
    # background generation written at t<=30
    assert "CrashRestored" in reasons
    for rec in live:
        assert rec.restored_from in first
        assert int(rec.restored_state["served"]) == \
            int(counters[rec.restored_from]["served"])


def test_double_eviction_parks_state_exactly_once(tmp_path):
    """Regression: a drain and a racing walltime-expiry fail hitting the
    same pod must park its checkpoint once — not feed two restores."""
    counters = {}
    cluster = mkcluster(2, chips=4, walltimes=[100.0, 0.0])
    cluster.apply_deployment(Deployment("svc", 1, template=PodTemplate(
        tolerations=list(TOL), request_chips=1,
        checkpoint_state=lambda name: counters.get(name))), 0.0)
    plane = ControlPlane(cluster)
    plane.nodes.ckpt_dir = str(tmp_path)
    plane.scheduler.scorers = [
        lambda rec, node, sched, now: 1.0 if node.name == "n0" else 0.0]
    plane.step(0.0)
    first = cluster.pods_of("svc")[0]
    counters[first.name] = {"served": 3}
    now = 50.0
    for n in cluster.nodes:
        cluster.heartbeat(n, now)
    plane.scheduler.scorers = []
    plane.nodes._drain_node("n0", now)
    plane.nodes._fail_node("n0", now, "walltime expired")   # racing path
    assert cluster.event_reasons(first.name).count("Evicted") == 1
    assert len(plane.deployments.pending_restores.get("svc", [])) == 1
    plane.deployments.reconcile(now)
    plane.scheduler.run_once(now)
    live = cluster.pods_of("svc")
    assert len(live) == 1 and live[0].bound
    assert int(live[0].restored_state["served"]) == 3


# ------------------------------------------------------ invariant audits

def test_auditor_green_on_healthy_cluster():
    cluster = mkcluster(2)
    cluster.submit(mkpod("p"), 0.0)
    ControlPlane(cluster).step(0.0)
    out = InvariantAuditor(cluster).audit(1.0)
    assert out["nodes"] == 2


def test_auditor_catches_quota_book_imbalance():
    cluster = mkcluster(1)
    aud = InvariantAuditor(cluster)
    aud.audit(0.0)
    # a ghost pod lands on the kubelet with no store record: node truth
    # and owner books diverge
    cluster.nodes["n0"].create_pod(mkpod("ghost"), 1.0)
    with pytest.raises(ChaosInvariantError):
        aud.audit(1.0)


def test_auditor_catches_duplicate_completion_and_double_booking():
    from types import SimpleNamespace
    cluster = mkcluster(1)
    dup = SimpleNamespace(runtimes={}, completed=[(7, 0.0), (7, 1.0)],
                          queue=[], _node_reachable=lambda name: True)
    with pytest.raises(ChaosInvariantError):
        InvariantAuditor(cluster, engine=dup).audit(1.0)
    from repro.data.pipeline import Request
    twice = SimpleNamespace(runtimes={}, completed=[],
                            queue=[Request(3, 0.0, 8, 4),
                                   Request(3, 0.0, 8, 4)],
                            _node_reachable=lambda name: True)
    with pytest.raises(ChaosInvariantError):
        InvariantAuditor(cluster, engine=twice).audit(2.0)


# ------------------------------------- capstone: partition + epoch fence

def _mk_engine(walltimes, service_rate=6.0, chips=2):
    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    nodes = [start_vk(f"n{i}", walltime=w, now=0.0,
                      slice_spec=SliceSpec(chips=chips))
             for i, w in enumerate(walltimes)]
    return StreamEngine(cfg, serving, nodes, service_rate=service_rate,
                        max_batch=4, record_tokens=True,
                        runtime_cfg=RuntimeConfig(max_batch=4, admit_tail=0))


def _setup(eng, ckpt_dir=None):
    eng.deploy(0.0)
    if ckpt_dir is not None:
        eng.plane.nodes.ckpt_dir = ckpt_dir
        eng.plane.nodes.bg_checkpoint_every = 10.0
    eng.cluster.scale("ersap", 2, 0.0, source="test")
    eng.reconcile(0.0)
    assert len(eng.pods) == 2
    assert len({p.node for p in eng.pods.values()}) == 2


def _drive(eng, ticks, dt=10.0, lam_until=8, injector=None, auditor=None):
    """Tick loop; returns every runtime incarnation ever live (so retired
    replicas' token logs stay inspectable)."""
    seen = {}
    for t in range(ticks):
        now = t * dt
        if injector is not None:
            injector.apply(eng.cluster, now)
        else:
            for name in eng.cluster.nodes:
                eng.cluster.heartbeat(name, now)
        eng.reconcile(now)
        eng.tick(now, dt, lam=1.0 if t < lam_until else 0.0)
        for rt in eng.runtimes.values():
            seen[id(rt)] = rt
        if auditor is not None:
            auditor.audit(now)
    return seen


def test_partition_rejoin_epoch_fence_token_identical(tmp_path):
    """Acceptance scenario: a serving node is partitioned mid-run long
    enough to be declared dead and its replica re-served elsewhere; on
    rejoin the stale replica is epoch-fenced. The chaos run loses zero
    requests, completes each exactly once, and every token any
    incarnation emitted is a prefix of the fault-free oracle's stream
    for that rid (deterministic prompt replay)."""
    oracle = _mk_engine([0.0, 0.0, 0.0])
    _setup(oracle)
    o_rts = _drive(oracle, 20)
    assert oracle.source.rid > 0
    assert len(oracle.completed) == oracle.source.rid
    o_logs = {}
    for rt in o_rts.values():
        for rid, log in rt.token_log.items():
            o_logs[rid] = list(log)        # fault-free: one incarnation/rid

    eng = _mk_engine([0.0, 0.0, 0.0])
    _setup(eng, ckpt_dir=str(tmp_path))
    victim = sorted(p.node for p in eng.pods.values())[0]
    victim_pods = {n for n, p in eng.pods.items() if p.node == victim}
    inj = FaultInjector([FaultSpec("partition", 30.0, victim, duration=90.0)])
    aud = InvariantAuditor(eng.cluster, engine=eng)
    rts = _drive(eng, 20, injector=inj, auditor=aud)
    assert aud.checks == 20

    # the partition ran its course: severed, declared dead, re-served,
    # rejoined, fenced
    reasons = eng.cluster.event_reasons()
    assert "Partitioned" in reasons and "Rejoined" in reasons
    fenced = [e for e in eng.cluster.events if e.reason == "Fenced"]
    assert fenced and all(e.name in victim_pods for e in fenced)
    assert eng.cluster.fence_epochs == {}           # floor consumed
    assert not eng.cluster.orphaned_pods(victim)    # kubelet cleaned up
    # the replica set is whole again and the victim's pod moved
    assert len(eng.pods) == 2
    moved = [r for r in eng.cluster.pods_of("ersap") if r.restored_from]
    assert any(r.restored_from in victim_pods for r in moved)

    # zero request loss, exactly-once completion
    assert eng.source.rid == oracle.source.rid      # identical workload
    done = [rid for rid, _ in eng.completed]
    assert len(done) == eng.source.rid
    assert len(set(done)) == len(done)
    assert not eng.queue

    # token identity vs the oracle: every incarnation's log is a prefix
    # of the oracle stream for that rid — replay, never divergence or
    # double-emission past the oracle's sequence
    compared = 0
    for rt in rts.values():
        for rid, log in rt.token_log.items():
            assert rid in o_logs
            assert list(log) == o_logs[rid][:len(log)], \
                f"rid {rid} diverged from the fault-free oracle"
            compared += 1
    assert compared > 0
