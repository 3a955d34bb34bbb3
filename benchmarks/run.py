"""Benchmark harness: one function per paper table/figure.

Output: ``name,us_per_call,derived`` CSV rows on stdout, plus a
machine-readable ``BENCH_run.json`` (every row) written next to the repo
root so the perf trajectory is tracked across PRs.
``bench_serving_throughput`` additionally persists ``BENCH_serving.json``
(chunked-vs-runtime tokens/s, trace counts).

  Table 6/7  -> bench_lifecycle_create / bench_lifecycle_monitor
  Eq.1/4.4.4 -> bench_hpa_formula
  4.4.5      -> bench_hpa_scaling
  Tables 8/9 -> bench_queue_16 / bench_queue_32 (M/M/1 sim vs Calc.Lq)
  Fig. 8     -> bench_dbn_tracking
  Fig. 9     -> bench_dbn_control
  5.1        -> bench_deployment_40
  4.5.4      -> bench_control_plane_churn (drain -> reschedule loop)
  §1/§4      -> bench_federation_churn (full-site kill, cross-site failover)
  QoS        -> bench_priority_spike (twin (replicas, priority) writes,
                batch preemption + resume, quota books balance)
  chaos      -> bench_chaos_soak (seeded fault storm vs fault-free
                oracle: zero loss, token-identical recovery, epoch
                fencing, balanced books every tick)
  serving    -> bench_serving_throughput (slot-slab runtime vs chunked)
             -> bench_paged_decode (paged KV slab vs dense slab)
             -> bench_prefix_reuse (prefix-sharing admission + spec decode)
  kernels    -> bench_kernel_* (interpret-mode Pallas vs jnp oracle)
  dry-run    -> bench_roofline (reads experiments/dryrun)

CLI: ``--only SUBSTR`` runs matching benches, ``--fast`` shrinks the
serving workload for CI smoke, ``--json-dir DIR`` relocates the JSONs.
"""
from __future__ import annotations

import json
import math
import pathlib
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
RESULTS: list = []
FAST = False
JSON_DIR = ROOT


def _timeit(fn, n=100, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6   # us


def row(name, us, derived):
    print(f"{name},{us:.1f},{derived}")
    metrics = {}
    for part in str(derived).split(";"):
        if "=" in part:
            k, v = part.split("=", 1)
            try:
                metrics[k] = float(v)
            except ValueError:
                metrics[k] = v
    RESULTS.append({"name": name, "us": round(us, 1), "derived": metrics})


def write_serving(key, report):
    """Merge one serving-bench report into BENCH_serving.json (a dict of
    bench-name -> report, so serving_throughput and paged_decode coexist
    and an --only run does not clobber the other's numbers)."""
    path = JSON_DIR / "BENCH_serving.json"
    merged = {}
    if path.exists():
        try:
            merged = json.loads(path.read_text())
        except (json.JSONDecodeError, OSError):
            merged = {}
    if "name" in merged:          # pre-PR-4 layout: one bare report
        merged = {merged["name"]: merged}
    merged[key] = report
    path.write_text(json.dumps(merged, indent=2) + "\n")


# ---------------------------------------------------------- Tables 6 & 7

def bench_lifecycle_create():
    from repro.core.state_machine import Container, create_pod_container

    def one():
        create_pod_container(Container("c"), 0.0)

    us = _timeit(one, n=2000)
    row("lifecycle_create_table6", us, f"pods_per_s={1e6 / us:.0f}")


def bench_lifecycle_monitor():
    from repro.core.jrm import start_vk
    from repro.core.state_machine import Container, Pod
    node = start_vk("vk", now=0.0)
    tol = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]
    for i in range(100):
        node.create_pod(Pod(f"p{i}", [Container("c")], tolerations=tol), 0.0)
    us = _timeit(lambda: node.get_pods(1.0), n=200)
    row("lifecycle_monitor_table7", us,
        f"pods_per_loop=100;loops_per_s={1e6 / us:.0f}")


# --------------------------------------------------------------- HPA

def bench_hpa_formula():
    from repro.core.hpa import desired_replicas
    us = _timeit(lambda: desired_replicas(4, 90, 50), n=10000)
    row("hpa_formula_eq1", us,
        f"example_4x90/50={desired_replicas(4, 90, 50)}")


def bench_hpa_scaling():
    """§4.4.5: load ramp up -> pods scale up; load drop -> scale down after
    the stabilization interval."""
    from repro.core.hpa import HPA, HPAConfig, MetricSample
    from repro.core.state_machine import Container, Pod, create_pod_container

    def mkpods(n, now):
        out = []
        for i in range(n):
            p = Pod(f"p{i}", [Container("c")])
            create_pod_container(p.containers[0], now)
            p.set_conditions_create(now)
            out.append(p)
        return out

    def scenario():
        hpa = HPA(HPAConfig(target=30.0, max_replicas=10,
                            cpu_initialization_period=0.0,
                            scale_down_stabilization=300.0))
        n, ups, downs = 1, 0, 0
        for t in range(0, 1200, 60):
            load = 90.0 if t < 400 else 10.0
            pods = mkpods(n, now=-1e4)
            samples = {p.name: MetricSample(load, timestamp=float(t))
                       for p in pods}
            d = hpa.evaluate(pods, samples, now=float(t))
            ups += d > n
            downs += d < n
            n = d
        return ups, downs, n

    us = _timeit(scenario, n=20)
    ups, downs, final = scenario()
    row("hpa_scaling_4.4.5", us,
        f"scale_ups={ups};scale_downs={downs};final={final}")


# --------------------------------------------------------- Tables 8 & 9

def _lindley_lq(lam, mu, n=400_000, seed=0):
    """M/M/1 L_q via Lindley recursion + Little's law."""
    rng = np.random.default_rng(seed)
    a = rng.exponential(1.0 / lam, n)     # interarrivals
    s = rng.exponential(1.0 / mu, n)      # services
    w = 0.0
    tot = 0.0
    for i in range(1, n):
        w = max(w + s[i - 1] - a[i], 0.0)
        tot += w
    return lam * tot / (n - 1)


def _bench_queue(threads):
    from repro.core.digital_twin.queue_model import MU_EXACT, table_for
    tab = table_for(threads)
    mu = MU_EXACT[threads]
    errs = []
    t0 = time.perf_counter()
    for state, lam, _m, _u, obs, calc in tab:
        sim = _lindley_lq(lam, mu, seed=int(state))
        errs.append(abs(sim - calc) / calc)
    us = (time.perf_counter() - t0) / len(tab) * 1e6
    row(f"queue_mm1_table{8 if threads == 16 else 9}", us,
        f"max_rel_err_vs_calc_lq={max(errs):.2f}")


def bench_queue_16():
    _bench_queue(16)


def bench_queue_32():
    _bench_queue(32)


# ------------------------------------------------------------ Figs 8 & 9

def _run_twin():
    from repro.core.digital_twin.control import ControlPolicy
    from repro.core.digital_twin.dbn import DigitalTwin
    from repro.core.digital_twin.queue_model import ground_truth, observe
    gt = ground_truth(80)
    twin, policy = DigitalTwin(), ControlPolicy()
    rng = np.random.default_rng(0)
    control, est, ctrl = 16, [], []
    for t, s in enumerate(gt):
        twin.assimilate(observe(s, control, rng), control)
        est.append(twin.estimate())
        control = policy.recommend(twin, control, t)
        ctrl.append(control)
    return gt, np.array(est), np.array(ctrl)


def bench_dbn_tracking():
    from repro.core.digital_twin.dbn import DigitalTwin
    twin = DigitalTwin()
    us = _timeit(lambda: twin.assimilate(50.0, 16), n=200)
    gt, est, _ = _run_twin()
    row("dbn_tracking_fig8", us,
        f"state_mae={np.abs(est - gt).mean():.3f}")


def bench_dbn_control():
    gt, _, ctrl = _run_twin()
    # predicted-vs-estimated agreement proxy: correct regime selection
    hi = np.mean(ctrl[gt >= 3.0] == 32)
    lo = np.mean(ctrl[gt <= 0.5] == 16)
    t0 = time.perf_counter()
    _run_twin()
    us = (time.perf_counter() - t0) * 1e6 / 80
    row("dbn_control_fig9", us,
        f"escalation_acc={hi:.2f};deescalation_acc={lo:.2f}")


# ------------------------------------------------------------------ §5.1

def bench_deployment_40():
    """§5.1 through the declarative control plane: 40 nodes registered in
    the Cluster store, a 40-replica Deployment declared, controllers +
    queue scheduler converge it in one reconcile step."""
    from repro.core.cluster import Cluster, Deployment, PodTemplate
    from repro.core.controllers import ControlPlane
    from repro.core.jcs import CentralService
    from repro.core.jfe import FrontEnd
    from repro.core.jfm import FacilityManager
    from repro.core.jrm import SliceSpec

    def scenario():
        fe = FrontEnd()
        wf = fe.add_wf("vk-nersc", 40, walltime=10800.0)
        jcs = CentralService(fe)
        jcs.launch_pilot(wf, now=0.0, slice_spec=SliceSpec(chips=4))
        cluster = Cluster()
        for n in jcs.node_list():
            cluster.register_node(n, 0.0)
            cluster.heartbeat(n.name, 120.0)
        FacilityManager().feed(cluster, 120.0)
        cluster.apply_deployment(Deployment(
            "ersap", 40, template=PodTemplate(
                tolerations=[{"key": "virtual-kubelet.io/provider",
                              "value": "mock"}],
                request_chips=4, request_hbm_bytes=8 << 30,
                expected_duration=3600.0)), 120.0)
        plane = ControlPlane(cluster)
        plane.step(120.0)
        bound = sum(1 for r in cluster.pods.values() if r.bound)
        return len(cluster.nodes), bound

    us = _timeit(scenario, n=5)
    nodes, bound = scenario()
    row("deployment_40node_5.1", us,
        f"nodes={nodes};pods_bound={bound};nodes_per_s={nodes / (us / 1e6):.0f}")


def bench_control_plane_churn():
    """Drain -> checkpoint -> evict -> reschedule loop (§4.5.4): half the
    nodes on short leases; the NodeLifecycleController drains them and the
    scheduler re-places every displaced replica on surviving nodes."""
    from repro.core.cluster import Cluster, Deployment, PodTemplate
    from repro.core.controllers import ControlPlane
    from repro.core.jrm import SliceSpec, start_vk

    def scenario():
        cluster = Cluster()
        for i in range(8):
            wall = 200.0 if i % 2 == 0 else 0.0     # half drain mid-run
            cluster.register_node(
                start_vk(f"n{i}", walltime=wall, now=0.0,
                         slice_spec=SliceSpec(chips=8)), 0.0)
        cluster.apply_deployment(Deployment(
            "ersap", 16, template=PodTemplate(
                tolerations=[{"key": "virtual-kubelet.io/provider",
                              "value": "mock"}],
                request_chips=2)), 0.0)
        plane = ControlPlane(cluster)
        moved = 0
        for t in range(0, 300, 20):
            now = float(t)
            for name in cluster.nodes:
                cluster.heartbeat(name, now)
            plane.step(now)
        moved = sum(1 for r in cluster.pods.values()
                    if r.restored_from is not None and r.bound)
        bound = sum(1 for r in cluster.pods.values() if r.bound)
        return bound, moved, len(cluster.events)

    us = _timeit(scenario, n=5)
    bound, moved, events = scenario()
    row("control_plane_churn_4.5.4", us,
        f"replicas_bound={bound};rescheduled={moved};events={events}")


def bench_federation_churn():
    """Full-site kill mid-stream (the §1/§4 cross-facility claim): serving
    replicas spread across two facilities by the site-aware scheduler;
    halfway through the stream the whole jlab pilot allocation is
    batch-drained in one checkpoint/evict wave (ControlPlane.drain_site)
    and its replicas reschedule at the surviving site with their slot
    tables restored. Asserts zero request loss and cross-site failover."""
    import jax
    from repro.configs.base import get_config
    from repro.core.cluster import Cluster
    from repro.core.controllers import ControlPlane
    from repro.core.elastic import ElasticServing
    from repro.core.jcs import CentralService
    from repro.core.jfe import FrontEnd
    from repro.core.jrm import SliceSpec
    from repro.core.scheduler import Scheduler, SiteTopology
    from repro.models import model_api as MA
    from repro.streaming.engine import StreamEngine

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)

    fe = FrontEnd()
    jcs = CentralService(fe)
    cluster = Cluster()
    # open-ended leases: the facility outage is the explicit drain_site
    # wave below, not a walltime expiry
    wfs = fe.add_multi_wf("fed-", {"jlab": 2, "nersc": 2}, nodetype="tpu",
                          walltime=0.0)
    jcs.launch_multi(wfs, now=0.0, slice_spec=SliceSpec(chips=2),
                     cluster=cluster)
    topo = SiteTopology.parse("jlab:nersc:40")
    plane = ControlPlane(cluster, scheduler=Scheduler(cluster, topology=topo))
    eng = StreamEngine(cfg, serving, jcs.node_list(), service_rate=6.0,
                       max_batch=4, cluster=cluster, plane=plane)
    eng.deploy(0.0)
    cluster.scale("ersap", 2, 0.0, source="bench")
    eng.reconcile(0.0)
    sites_before = sorted({cluster.nodes[p.node].site
                           for p in eng.pods.values()})
    assert sites_before == ["jlab", "nersc"], "site spread failed"

    dt = 10.0
    ticks = 8 if FAST else 16
    kill_at = ticks // 2
    t0 = time.perf_counter()
    for t in range(ticks + 6):
        now = t * dt
        if t == kill_at:
            plane.drain_site("jlab", now)     # facility gone, one wave
        for name, node in cluster.nodes.items():
            if node.site != "jlab" or t < kill_at:
                cluster.heartbeat(name, now)
        eng.reconcile(now)
        eng.tick(now, dt, lam=1.0 if t < ticks else 0.0)
    s = time.perf_counter() - t0

    lost = eng.source.rid - len(eng.completed)
    moved = sum(1 for r in cluster.pods_of("ersap")
                if r.restored_from is not None and r.bound)
    sites_after = sorted({cluster.nodes[p.node].site
                          for p in eng.pods.values()})
    assert lost == 0, f"{lost} requests lost across the site kill"
    assert sites_after == ["nersc"], "replicas did not fail over cross-site"
    assert moved >= 1
    row("federation_churn", s / (ticks + 6) * 1e6,
        f"requests={eng.source.rid};completed={len(eng.completed)};"
        f"lost={lost};rescheduled_cross_site={moved};"
        f"sites_before={'+'.join(sites_before)};"
        f"sites_after={'+'.join(sites_after)}")


def bench_priority_spike():
    """QoS under a mixed-tenant pressure spike: a preemptible batch
    tenant saturates the cluster's chips next to one serving replica;
    mid-run the arrival rate spikes past one replica's capacity. The
    digital twin escalates the serving Deployment along the (replicas,
    priority) action space — ``standard`` -> ``latency-critical`` plus a
    2x replica write — and the scale-up replica *preempts* a batch pod
    (checkpoint -> requeue, §4.5.4 path). When the spike passes, serving
    de-escalates and the preempted batch pod reschedules and resumes
    from its checkpointed progress.

    Asserts (the QoS acceptance criteria): zero serving-request loss;
    serving p99 latency bounded; batch state round-trips identically
    through preempt -> requeue -> resume; only batch (never serving,
    never equal-or-higher priority) is preempted; and the fair-share
    quota books balance (used + free == capacity, per-owner sums match
    the node truth) on every tick."""
    import jax
    from repro.configs.base import get_config
    from repro.core.cluster import Cluster
    from repro.core.controllers import ControlPlane
    from repro.core.digital_twin.control import ControlPolicy
    from repro.core.elastic import ElasticServing
    from repro.core.jrm import SliceSpec, start_vk
    from repro.core.qos import BatchTenant, Quota
    from repro.models import model_api as MA
    from repro.streaming.engine import StreamEngine

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)

    cluster = Cluster()
    for i in range(4):
        cluster.register_node(
            start_vk(f"n{i}", nodetype="tpu", now=0.0,
                     slice_spec=SliceSpec(chips=2)), 0.0)
        cluster.heartbeat(f"n{i}", 0.0)
    cluster.apply_quota(Quota(owner="ersap", chips=4), 0.0)
    cluster.apply_quota(Quota(owner="batch", chips=7), 0.0)
    plane = ControlPlane(cluster)

    eng = StreamEngine(cfg, serving, list(cluster.nodes.values()),
                       service_rate=2.0, max_batch=4,
                       cluster=cluster, plane=plane)
    # paper control regions (Tables 8/9 put E[Lq|16] between ~34 and 248:
    # the spike must push the queue into the state-3 regime to escalate,
    # and the post-spike drain back under lq_low de-escalates)
    eng.policy = ControlPolicy(lq_high=55.0, lq_low=40.0)
    eng.deploy(0.0)
    assert len(eng.pods) == 1

    # batch tenant: both tenants start at *standard* — preemption is only
    # possible after the twin's priority write, which is the point
    batch = BatchTenant(cluster, 7, priority_class="standard")
    eng.reconcile(0.0)
    assert batch.bound == 7
    cluster.ledger.assert_balanced()

    arrivals = {}
    real_arrivals = eng.source.arrivals

    def tracked(now, dt, lam, **kw):
        out = real_arrivals(now, dt, lam, **kw)
        for r in out:
            arrivals[r.rid] = r.arrival
        return out

    eng.source.arrivals = tracked

    dt = 10.0
    ticks = 16 if FAST else 24
    spike = range(ticks // 4, ticks // 2)        # §6.2-style pressure spike
    t0 = time.perf_counter()
    for t in range(ticks + 8):                   # +8 drain ticks (lam=0)
        now = t * dt
        lam = 0.0 if t >= ticks else (4.5 if t in spike else 0.6)
        for name in cluster.nodes:
            cluster.heartbeat(name, now)
        eng.reconcile(now)
        batch.advance()                          # batch work progresses
        eng.tick(now, dt, lam)
        if t % 2 == 1:
            eng.control_step(now)
        cluster.ledger.assert_balanced()         # quota books, every tick
    elapsed = time.perf_counter() - t0

    # zero serving-request loss across escalate -> preempt -> de-escalate
    lost = eng.source.rid - len(eng.completed)
    assert lost == 0, f"{lost} serving requests lost"
    assert len(eng.queue) == 0
    lat = np.asarray([done - arrivals[rid] for rid, done in eng.completed])
    p99 = float(np.percentile(lat, 99))
    assert p99 <= 12 * dt, f"serving p99 {p99:.0f}s unbounded under spike"
    # the twin's priority write landed and enabled preemption of batch only
    reasons = cluster.event_reasons()
    assert "PriorityChanged" in reasons
    preempted = [ev for ev in cluster.events if ev.reason == "Preempted"]
    assert preempted, "pressure spike never triggered preemption"
    assert all(ev.name.startswith("batch") for ev in preempted), \
        "a non-batch (equal-or-higher priority) pod was preempted"
    # preempted batch pods resumed with state identical to the checkpoint
    # (each resume validated against its own eviction's snapshot)
    assert batch.resumed, "no preempted batch pod resumed"
    assert not batch.mismatches, \
        f"resume/checkpoint state mismatches: {batch.mismatches}"
    escalated = sum(1 for ev in cluster.events
                    if ev.reason == "PriorityChanged")
    row("priority_spike", elapsed / (ticks + 8) * 1e6,
        f"requests={eng.source.rid};lost={lost};p99_s={p99:.1f};"
        f"preempted={len(preempted)};batch_resumed={len(batch.resumed)};"
        f"priority_writes={escalated};quota_balanced=1")


# ------------------------------------------------------------ chaos soak

def bench_chaos_soak():
    """Serving + batch mix under a seeded fault storm (flap, straggler,
    partition, checkpoint corruption, walltime cut, crash — composed
    with a flash-crowd surge through the RequestSource seam) vs a
    fault-free oracle run over the identical workload.

    Asserts the robustness acceptance criteria: zero request loss and
    exactly-once completion; every token any replica incarnation emitted
    is a prefix of the oracle's stream for that rid (deterministic prompt
    replay — no divergence, no double emission); the partitioned node's
    stale replica is epoch-fenced on rejoin; quota-ledger, page-allocator
    and rid books balance on *every* tick (InvariantAuditor); batch
    progress rolls back at most the background-checkpoint interval; and
    service recovery latency after any fault stays bounded. The chaos
    side runs under two storm seeds so wildcard targeting cannot
    overfit one lucky draw."""
    import tempfile

    import jax
    from repro.configs.base import get_config
    from repro.core.chaos import FaultInjector, FaultSpec, InvariantAuditor
    from repro.core.cluster import Cluster
    from repro.core.controllers import ControlPlane
    from repro.core.elastic import ElasticServing
    from repro.core.jrm import SliceSpec, start_vk
    from repro.core.qos import BatchTenant
    from repro.models import model_api as MA
    from repro.streaming.engine import StreamEngine
    from repro.streaming.runtime import RuntimeConfig

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)

    dt = 10.0
    ticks = 36 if FAST else 56
    drain = 10
    recovery_bound_s = 60.0          # stale_after (30) + detection slack
    rollback_bound = 6               # progress units vs bg interval of 1

    def run_side(schedule, seed):
        cluster = Cluster()
        for i in range(5):
            cluster.register_node(
                start_vk(f"n{i}", nodetype="tpu", now=0.0,
                         slice_spec=SliceSpec(chips=2)), 0.0)
            cluster.heartbeat(f"n{i}", 0.0)
        plane = ControlPlane(cluster)
        ckpt_root = tempfile.mkdtemp(prefix="chaos-soak-")
        plane.nodes.ckpt_dir = ckpt_root
        plane.nodes.bg_checkpoint_every = dt
        plane.nodes.drain_pods_per_tick = 1
        eng = StreamEngine(cfg, serving, list(cluster.nodes.values()),
                           service_rate=4.0, max_batch=4,
                           cluster=cluster, plane=plane, record_tokens=True,
                           runtime_cfg=RuntimeConfig(max_batch=4,
                                                     admit_tail=0))
        eng.deploy(0.0)
        cluster.scale("ersap", 2, 0.0, source="bench")
        eng.reconcile(0.0)
        assert len(eng.pods) == 2
        batch = BatchTenant(cluster, 3, priority_class="batch")
        eng.reconcile(0.0)
        assert batch.bound == 3
        # the partition must sever a live serving replica so the
        # fence path is exercised, not just the wildcard lottery
        victim = sorted(p.node for p in eng.pods.values())[0]
        # flash crowd composed with the fault storm: the surge fires on
        # BOTH sides (load is not a failure), so the oracle sees the
        # identical arrival stream and rid accounting stays comparable
        surge = FaultSpec("surge", 150.0, "ersap", duration=80.0,
                          magnitude=2.0)
        inj = FaultInjector(
            [FaultSpec("partition", 100.0, victim, duration=100.0), surge]
            + list(schedule), seed=seed, ckpt_dir=ckpt_root
        ) if schedule is not None else FaultInjector([surge], seed=seed)
        aud = InvariantAuditor(cluster, engine=eng)
        seen_rts, gap, worst_gap = {}, 0, 0
        for t in range(ticks + drain):
            now = t * dt
            inj.apply(cluster, now)
            eng.source.surge = inj.surge_factor("ersap")
            eng.reconcile(now)
            batch.advance()
            eng.tick(now, dt, lam=0.8 if t < ticks else 0.0)
            for rt in eng.runtimes.values():
                seen_rts[id(rt)] = rt
            aud.audit(now)
            healthy = sum(
                1 for p in eng.pods.values()
                if cluster.node_status[p.node].reachable
                and cluster.node_status[p.node].ready)
            gap = gap + 1 if healthy < 2 else 0
            worst_gap = max(worst_gap, gap)
        return eng, batch, aud, seen_rts, worst_gap * dt

    storm = ["flap:*@40+20", "straggler:*@60+40x6", "ckpt_corrupt:*@230",
             "walltime_cut:*@240x10", "crash:*@300"]

    # fault-free oracle: the reference token streams + workload totals
    oracle, _, _, o_rts, _ = run_side(None, seed=0)
    assert len(oracle.completed) == oracle.source.rid > 0
    o_logs = {}
    for rt in o_rts.values():
        for rid, log in rt.token_log.items():
            o_logs[rid] = list(log)

    t0 = time.perf_counter()
    worst_recovery, fenced_total, restored_total, compared = 0.0, 0, 0, 0
    max_rollback = 0
    for seed in (0, 1):
        eng, batch, aud, rts, recovery_s = run_side(storm, seed)
        cluster = eng.cluster
        # zero loss, exactly-once (the auditor also checked every tick)
        assert eng.source.rid == oracle.source.rid
        done = [rid for rid, _ in eng.completed]
        lost = eng.source.rid - len(done)
        assert lost == 0, f"seed {seed}: {lost} requests lost"
        assert len(set(done)) == len(done), f"seed {seed}: duplicates"
        assert not eng.queue
        assert aud.checks == ticks + drain
        # epoch fence: severed replica fenced on rejoin, floor consumed
        fenced = [e for e in cluster.events if e.reason == "Fenced"]
        assert fenced, f"seed {seed}: partition rejoin never fenced"
        assert cluster.fence_epochs == {}
        fenced_total += len(fenced)
        restored_total += sum(1 for e in cluster.events
                              if e.reason == "CrashRestored")
        # token identity vs the oracle (prefix replay, never divergence)
        for rt in rts.values():
            for rid, log in rt.token_log.items():
                assert rid in o_logs
                assert list(log) == o_logs[rid][:len(log)], \
                    f"seed {seed}: rid {rid} diverged from oracle"
                compared += 1
        # batch survived the storm; rollback bounded by the bg interval
        assert batch.bound == 3, f"seed {seed}: batch pods lost"
        for name, got, exp in batch.mismatches:
            assert 0 <= exp - got <= rollback_bound, \
                f"seed {seed}: {name} rolled back {exp - got} (> bound)"
            max_rollback = max(max_rollback, exp - got)
        assert recovery_s <= recovery_bound_s, \
            f"seed {seed}: recovery took {recovery_s:.0f}s"
        worst_recovery = max(worst_recovery, recovery_s)
    elapsed = time.perf_counter() - t0

    assert compared > 0
    row("chaos_soak", elapsed / (2 * (ticks + drain)) * 1e6,
        f"requests={oracle.source.rid};lost=0;duplicates=0;"
        f"token_prefix_checked={compared};fenced={fenced_total};"
        f"crash_restored={restored_total};max_rollback={max_rollback};"
        f"recovery_worst_s={worst_recovery:.0f};"
        f"recovery_bound_s={recovery_bound_s:.0f};"
        f"audit_ticks={2 * (ticks + drain)};seeds=2")


def bench_overload_brownout():
    """Overload protection & graceful degradation (ISSUE-9 capstone):
    a flash crowd 10x past aggregate capacity hits a deadline-stamped,
    tiered request mix — run three ways over the *identical* arrival
    stream: (1) the protected stack (bounded queue with lowest-tier-first
    rejection, retry budgets, brownout watermarks shedding batch ->
    standard while capping output length and disabling speculative
    decode, replica breaker armed), (2) an unprotected baseline (same
    capacity, no protection), and (3) an unloaded oracle (ample
    capacity) for the reference token streams. A second scenario kills
    a whole site at the surge peak and pays the cost-modeled checkpoint
    transfer window while serving degraded.

    Assertion gates (this bench is part of ``--check``): the protected
    run completes every latency-critical request within the SLO with
    zero LC sheds and a shed fraction under the declared bound, while
    the unprotected baseline demonstrably violates the LC SLO; every
    admitted request's output is token-identical (prefix under
    degradation caps) to the unloaded oracle; the queue stays bounded
    where the baseline's grows past it; nothing is lost — every request
    either completes exactly once or is an explicit shed with a reason;
    site loss at peak fires a SiteDrainTransfer window, replicas fail
    over cross-site, and LC protection holds throughout."""
    import jax
    from repro.configs.base import get_config
    from repro.core import qos
    from repro.core.chaos import FaultInjector, FaultSpec
    from repro.core.cluster import Cluster
    from repro.core.controllers import ControlPlane
    from repro.core.elastic import ElasticServing
    from repro.core.jrm import SliceSpec, start_vk
    from repro.core.scheduler import Scheduler, SiteTopology
    from repro.models import model_api as MA
    from repro.streaming.engine import StreamEngine
    from repro.streaming.runtime import RuntimeConfig

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)

    dt = 10.0
    ticks = 26 if FAST else 40
    drain = 16
    lam = 0.8                          # 8/tick base, 80/tick at peak
    slo = 6 * dt                       # latency-critical completion SLO
    shed_bound = 0.80                  # declared shed-fraction ceiling
    queue_cap = 240
    LC = qos.LATENCY_CRITICAL.value
    tiers = ((qos.BATCH.value, 0.45), (qos.STANDARD.value, 0.45),
             (LC, 0.10))
    # flash crowd through the chaos seam: 10x for 10 ticks vs a 2-replica
    # aggregate capacity of 40 req/tick — 2x past saturation at peak
    surge = FaultSpec("surge", 6 * dt, "ersap", duration=10 * dt,
                      magnitude=10.0)
    kill_tick = 10                     # scenario B: site loss at peak

    def run_side(protected, *, two_sites=False, kill_at=None,
                 service_rate=2.0):
        cluster = Cluster()
        topo = None
        if two_sites:
            # register jlab only, so both replicas deterministically bind
            # there; nersc comes up after placement (the failover target)
            topo = SiteTopology.parse("jlab:nersc:40", "",
                                      "jlab:nersc:1e-06")
            for i in range(2):
                cluster.register_node(
                    start_vk(f"j{i}", nodetype="tpu", site="jlab", now=0.0,
                             slice_spec=SliceSpec(chips=2)), 0.0)
                cluster.heartbeat(f"j{i}", 0.0)
            plane = ControlPlane(cluster,
                                 scheduler=Scheduler(cluster,
                                                     topology=topo))
        else:
            for i in range(4):
                cluster.register_node(
                    start_vk(f"n{i}", nodetype="tpu", now=0.0,
                             slice_spec=SliceSpec(chips=2)), 0.0)
                cluster.heartbeat(f"n{i}", 0.0)
            plane = ControlPlane(cluster)
        eng = StreamEngine(cfg, serving, list(cluster.nodes.values()),
                           service_rate=service_rate, max_batch=4,
                           cluster=cluster, plane=plane, record_tokens=True,
                           runtime_cfg=RuntimeConfig(max_batch=4,
                                                     admit_tail=0))
        eng.source.tiers = tiers
        if protected:
            eng.source.ttl = slo       # deadline-aware admission
            eng.queue_cap = queue_cap
            eng.brownout = qos.BrownoutController(
                delay_target_s=2 * dt, dwell_ticks=1, recover_ticks=2,
                degrade_max_new=4)
            eng.retry_budget = qos.RetryBudget(rate=0.5, burst=20.0)
            eng.breaker = qos.ReplicaBreaker(probe_after_s=3 * dt)
        eng.deploy(0.0)
        cluster.scale("ersap", 2, 0.0, source="bench")
        eng.reconcile(0.0)
        assert len(eng.pods) == 2
        if two_sites:
            assert all(cluster.nodes[p.node].site == "jlab"
                       for p in eng.pods.values())
            for i in range(2):
                cluster.register_node(
                    start_vk(f"c{i}", nodetype="tpu", site="nersc", now=0.0,
                             slice_spec=SliceSpec(chips=2)), 0.0)
                cluster.heartbeat(f"c{i}", 0.0)
        # track (arrival, priority) per rid for SLO accounting; deferred
        # re-releases keep their original stamp via setdefault
        meta = {}
        orig = eng.source.arrivals

        def tracked(t_now, t_dt, t_lam, **kw):
            out = orig(t_now, t_dt, t_lam, **kw)
            for r in out:
                meta.setdefault(r.rid, (r.arrival, r.priority))
            return out

        eng.source.arrivals = tracked
        inj = FaultInjector([surge], seed=0)
        rts, qmax = {}, 0
        for t in range(ticks + drain):
            now = t * dt
            inj.apply(cluster, now)
            eng.source.surge = inj.surge_factor("ersap")
            if kill_at is not None and t == kill_at:
                plane.drain_site("jlab", now)   # facility gone at peak
            for name, node in cluster.nodes.items():
                if kill_at is None or node.site != "jlab" or t < kill_at:
                    cluster.heartbeat(name, now)
            eng.reconcile(now)
            eng.tick(now, dt, lam=lam if t < ticks else 0.0)
            qmax = max(qmax, len(eng.queue))
            for rt in eng.runtimes.values():
                rts[id(rt)] = rt
        return eng, meta, rts, qmax

    def lc_violations(eng, meta):
        done = dict(eng.completed)
        viol = 0
        for rid, (arr, prio) in meta.items():
            if prio < LC:
                continue
            end = done.get(rid)
            if end is None or end - arr > slo:
                viol += 1
        return viol

    # unloaded oracle: same arrival stream, ample capacity — reference
    # token streams and the proof the workload itself is servable
    oracle, o_meta, o_rts, _ = run_side(False, service_rate=50.0)
    assert len(oracle.completed) == oracle.source.rid > 0
    o_logs = {}
    for rt in o_rts.values():
        for rid, log in rt.token_log.items():
            o_logs[rid] = list(log)

    t0 = time.perf_counter()
    prot, p_meta, p_rts, p_qmax = run_side(True)
    unprot, u_meta, _, u_qmax = run_side(False)
    fail, f_meta, _, _ = run_side(True, two_sites=True, kill_at=kill_tick)
    elapsed = time.perf_counter() - t0

    # identical arrival streams across all sides (protection knobs and
    # deferral never touch the RNG)
    assert prot.source.rid == oracle.source.rid == unprot.source.rid

    # exactly-once + explicit-shed accounting: nothing vanishes
    shed_rids = {rid for rid, _, _ in prot.shed}
    done = [rid for rid, _ in prot.completed]
    assert len(set(done)) == len(done), "duplicate completion"
    assert not (set(done) & shed_rids), "completed AND shed"
    assert len(done) + len(shed_rids) == prot.source.rid, "requests lost"
    assert not prot.queue and not prot.source._deferred

    # the headline gates: protected holds the LC SLO with zero sheds of
    # LC traffic and bounded shed fraction; unprotected collapses
    assert lc_violations(prot, p_meta) == 0, "protected run broke LC SLO"
    for rid, reason, _ in prot.shed:
        assert p_meta[rid][1] < LC, f"latency-critical rid {rid} shed"
    shed_frac = len(shed_rids) / prot.source.rid
    assert 0 < shed_frac <= shed_bound, f"shed_frac={shed_frac:.2f}"
    u_viol = lc_violations(unprot, u_meta)
    assert u_viol > 0, "baseline did not collapse — overload too weak"
    # brownout actually escalated (and staged back down), queue stayed
    # bounded where the baseline's grew past the cap
    assert any(new >= 2 for _, _, new, _ in prot.brownout.transitions)
    assert prot.brownout.level <= 1, "brownout never recovered"
    assert p_qmax <= queue_cap and u_qmax > queue_cap
    assert prot.rejected_total > 0 and prot.retried_total > 0

    # token identity: every admitted request's output is a prefix of the
    # unloaded oracle's stream (degradation caps length, never content)
    compared = 0
    for rt in p_rts.values():
        for rid, log in rt.token_log.items():
            assert rid in o_logs
            assert list(log) == o_logs[rid][:len(log)], \
                f"rid {rid} diverged from oracle under degradation"
            compared += 1
    assert compared > 0

    # scenario B: site loss at the surge peak — cost-modeled transfer
    # window fired, replicas failed over cross-site, LC protection held,
    # accounting stayed exact
    assert fail.transfer_windows >= 1 and fail.plane.last_transfer_s > 0
    assert any(e.reason == "SiteDrainTransfer"
               for e in fail.cluster.events)
    assert sorted({fail.cluster.nodes[p.node].site
                   for p in fail.pods.values()}) == ["nersc"]
    assert lc_violations(fail, f_meta) == 0, "LC SLO broke during failover"
    f_done = {rid for rid, _ in fail.completed}
    f_shed = {rid for rid, _, _ in fail.shed}
    assert len(f_done) + len(f_shed) == fail.source.rid

    row("overload_brownout", elapsed / (3 * (ticks + drain)) * 1e6,
        f"requests={prot.source.rid};lc_viol_protected=0;"
        f"lc_viol_baseline={u_viol};shed_frac={shed_frac:.2f};"
        f"shed_bound={shed_bound:.2f};retried={prot.retried_total};"
        f"rejected={prot.rejected_total};"
        f"shed_by={','.join(f'{k}:{v}' for k, v in sorted(prot.shed_counts.items()))};"
        f"qmax_protected={p_qmax};qmax_baseline={u_qmax};"
        f"brownout_transitions={len(prot.brownout.transitions)};"
        f"token_prefix_checked={compared};"
        f"failover_window_s={fail.plane.last_transfer_s:.1f}")


def bench_scale_bringup():
    """Event-driven control plane at 10k-node / 50k-pod scale (ISSUE-8):
    bring the fleet up, then run a churn phase — full heartbeat storms
    every tick plus evictions, walltime cuts and straggler flips — and
    report watch-bus throughput (deltas dispatched per second), per-tick
    reconcile latency, and a machine-independent polling-vs-event
    steady-state speedup measured head-to-head in one process.

    Internal assertion gates (this bench is part of ``--check``): every
    replica binds after bring-up AND after the churn settles, the
    incremental capacity index verifies against a from-scratch recompute
    at the end, bus throughput stays above an absolute floor set far
    below any healthy interpreter, and the event plane's steady-state
    tick beats polling by a comfortable margin (the point of the
    refactor: reconcile work scales with the *delta rate*, not the
    fleet size)."""
    from repro.core.cluster import Cluster, Deployment, PodTemplate
    from repro.core.controllers import ControlPlane
    from repro.core.jrm import SliceSpec, start_vk

    n_nodes = 2_000 if FAST else 10_000
    n_deps = 20 if FAST else 50
    per_dep = 500 if FAST else 1_000          # pods = n_deps * per_dep
    churn_ticks = 6 if FAST else 20
    # absolute churn-phase floor, set ~3-5x below healthy interpreter
    # rates (34k/s fast, 125k/s full on the dev box) so only a genuine
    # regression — not a slow CI runner — trips it
    events_floor = 10_000.0 if FAST else 25_000.0
    speedup_floor = 2.0                       # steady-state tick, evt vs poll
    sites = [f"site{i}" for i in range(8)]
    tol = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]
    n_pods = n_deps * per_dep

    t0 = time.perf_counter()
    cluster = Cluster()
    plane = ControlPlane(cluster)
    for i in range(n_nodes):
        cluster.register_node(
            start_vk(f"n{i}", site=sites[i % len(sites)],
                     slice_spec=SliceSpec(chips=8)), 0.0)
        cluster.heartbeat(f"n{i}", 0.0)
    for d in range(n_deps):
        cluster.apply_deployment(Deployment(
            f"d{d}", per_dep, template=PodTemplate(
                labels={"app": f"d{d}"}, tolerations=list(tol),
                request_chips=1)), 0.0)
    now = 0.0
    for _ in range(5):
        plane.step(now)
        now += 10.0
        if sum(1 for r in cluster.pods.values() if r.bound) == n_pods:
            break
    bound = sum(1 for r in cluster.pods.values() if r.bound)
    assert bound == n_pods, f"bring-up stalled at {bound}/{n_pods}"
    bringup_s = time.perf_counter() - t0

    # churn: every node heartbeats every tick (the bus load that made
    # polling necessary in the first place), plus evictions that should
    # wake parked work, walltime cuts that drain, straggler flips that
    # regroup the index
    names = list(cluster.nodes)
    tick_s = []
    churn_from = cluster.deltas_dispatched
    for t in range(churn_ticks):
        now += 10.0
        s = time.perf_counter()
        for n in names:
            cluster.heartbeat(n, now)
        pods = list(cluster.pods)
        stride = max(1, len(pods) // 50)
        for name in pods[(t * 37) % stride::stride][:50]:
            cluster.evict(name, now)
        cluster.cut_walltime(f"n{(t * 13 + 1) % n_nodes}", now, 30.0)
        for i in range(5):
            nd = f"n{(t * 101 + i * 7) % n_nodes}"
            st = cluster.node_status[nd]
            cluster.set_node_status(nd, now, ready=st.ready,
                                    straggler=not st.straggler)
        plane.step(now)
        tick_s.append(time.perf_counter() - s)
    # bus throughput over the churn phase only: bring-up wall time is
    # dominated by the 50k actual binds (pod objects, containers, the
    # ledger), which is placement work, not event pumping
    events_per_s = (cluster.deltas_dispatched - churn_from) / sum(tick_s)
    # settle: drained/evicted replicas must all re-bind
    for _ in range(30):
        now += 10.0
        for n in names:
            cluster.heartbeat(n, now)
        plane.step(now)
        if sum(1 for r in cluster.pods.values() if r.bound) == n_pods:
            break
    bound = sum(1 for r in cluster.pods.values() if r.bound)
    assert bound == n_pods, f"churn never settled: {bound}/{n_pods}"
    plane.scheduler._index.verify(now)
    elapsed = time.perf_counter() - t0
    assert events_per_s >= events_floor, \
        f"bus throughput {events_per_s:.0f}/s below floor {events_floor:.0f}"

    # head-to-head: identical steady-state cluster (all replicas bound,
    # every node heartbeating), one tick measured under each plane. The
    # polling tick scans the whole fleet; the event tick does O(deltas).
    def steady_tick_us(polling):
        c = Cluster()
        p = ControlPlane(c, polling=polling)
        small = 300 if FAST else 400
        for i in range(small):
            c.register_node(
                start_vk(f"m{i}", slice_spec=SliceSpec(chips=4)), 0.0)
            c.heartbeat(f"m{i}", 0.0)
        c.apply_deployment(Deployment("svc", small * 2, template=PodTemplate(
            labels={"app": "svc"}, tolerations=list(tol),
            request_chips=1)), 0.0)
        p.step(0.0)
        assert sum(1 for r in c.pods.values() if r.bound) == small * 2
        state = {"now": 0.0}

        def tick():
            state["now"] += 10.0
            for n in c.nodes:
                c.heartbeat(n, state["now"])
            p.step(state["now"])

        return _timeit(tick, n=20 if FAST else 40, warmup=3)

    poll_us = steady_tick_us(polling=True)
    evt_us = steady_tick_us(polling=False)
    steady_speedup = poll_us / evt_us
    assert steady_speedup >= speedup_floor, \
        f"steady-state speedup {steady_speedup:.1f}x < {speedup_floor}x"

    lat = sorted(tick_s)
    p50 = lat[len(lat) // 2] * 1e3
    p99 = lat[min(len(lat) - 1, int(len(lat) * 0.99))] * 1e3
    row("scale_bringup", sum(tick_s) / len(tick_s) * 1e6,
        f"nodes={n_nodes};pods={n_pods};bringup_s={bringup_s:.2f};"
        f"total_s={elapsed:.2f};"
        f"events_dispatched={cluster.deltas_dispatched};"
        f"events_per_s={events_per_s:.0f};events_floor={events_floor:.0f};"
        f"churn_tick_p50_ms={p50:.1f};churn_tick_p99_ms={p99:.1f};"
        f"steady_poll_us={poll_us:.0f};steady_event_us={evt_us:.0f};"
        f"steady_speedup={steady_speedup:.2f};"
        f"speedup_floor={speedup_floor};fast={FAST}")


# ------------------------------------------------------- serving runtime

def bench_serving_throughput():
    """Slot-slab continuous-batching runtime vs the pre-PR chunked path on
    qwen2-7b ``.reduced()``: same request set (randomized prompt_len /
    max_new), tokens/s of *useful* tokens (sum of max_new). Both paths get
    a warm-up pass so the headline number is steady-state; cold (compiling)
    pass time is reported alongside — retrace avoidance is most of the
    cold-path story. Persists BENCH_serving.json."""
    import jax
    from repro.configs.base import get_config
    from repro.core.elastic import ElasticServing
    from repro.core.jrm import SliceSpec, start_vk
    from repro.data.pipeline import RequestSource
    from repro.models import model_api as MA
    from repro.streaming.engine import StreamEngine

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    n_req = 24 if FAST else 96

    def request_set():
        # mixed generation lengths: the workload where chunked serving
        # over-decodes every request to its chunk's max (and where the
        # runtime's per-slot accounting pays exact cost)
        src = RequestSource(seed=7, prompt_range=(8, 48),
                            max_new_range=(2, 32))
        return src.arrivals(0.0, 1.0, lam=float(n_req))

    def run_path(use_runtime):
        nodes = [start_vk("bench-n0", now=0.0,
                          slice_spec=SliceSpec(chips=4))]
        eng = StreamEngine(cfg, serving, nodes, service_rate=1e9,
                           max_batch=8, use_runtime=use_runtime)
        eng.deploy(0.0)

        def one_pass(now):
            eng.queue.extend(request_set())
            t0 = time.perf_counter()
            eng.tick(now, 1.0, lam=0.0)
            return time.perf_counter() - t0

        # fast mode feeds the --check guard: more warm samples tighten the
        # min against co-tenant noise on shared runners
        n_pass = 7 if FAST else 4
        cold = one_pass(0.0)
        warm = min(one_pass(float(t)) for t in range(1, n_pass))
        tokens = sum(r.max_new for r in request_set())
        out = {"cold_s": round(cold, 4), "s": round(warm, 4),
               "tok_per_s": round(tokens / warm, 1), "useful_tokens": tokens}
        if use_runtime and eng.runtimes:
            rt = next(iter(eng.runtimes.values()))
            out["traces"] = dict(rt.kernels.trace_counts)
            out["trace_bound"] = rt.kernels.max_traces
        assert len(eng.completed) == n_pass * len(request_set())
        return out

    chunked = run_path(False)
    runtime = run_path(True)
    speedup = chunked["s"] / runtime["s"]
    cold_speedup = chunked["cold_s"] / runtime["cold_s"]
    report = {"name": "serving_throughput", "arch": f"{cfg.name}.reduced",
              "requests": n_req, "fast": FAST, "chunked": chunked,
              "runtime": runtime, "speedup": round(speedup, 2),
              "cold_speedup": round(cold_speedup, 2)}
    write_serving("serving_throughput", report)
    row("serving_throughput", runtime["s"] * 1e6,
        f"runtime_tok_per_s={runtime['tok_per_s']};"
        f"chunked_tok_per_s={chunked['tok_per_s']};"
        f"speedup={speedup:.2f};cold_speedup={cold_speedup:.2f};"
        f"admit_traces={runtime['traces']['admit']};"
        f"decode_traces={runtime['traces']['decode']}")


def bench_paged_decode():
    """Length-proportional decode (paged KV slab) vs the PR-2 dense slab on
    a length-skewed, short-heavy request mix (varied ``max_new``) — the
    workload where the dense slab wastes the most: every row pays
    full-capacity attention/HBM no matter how short its request. Four
    runtimes, same model, same requests:

      dense       max_batch=8, per-slot capacity slab, plain full-width
                  attention — the PR-2 configuration (the baseline)
      dense_skip  same slab, jnp block-skip decode (this PR's dispatch
                  layer on the old layout: compute already tracks the
                  deepest live row, HBM still rows x capacity)
      paged       max_batch=8, paged pool — decode reads only the live
                  kv bucket, admission allocates per-request footprints
      paged_wide  equal-HBM configuration: the pool holds exactly the
                  dense slab's KV entries, but short-request footprints
                  let max_batch grow 3x — the PagedAttention batch story

    Steady-state tokens/s per path; ``speedup`` (headline, asserted >=1.5x
    by --check) is paged_wide vs dense at equal HBM. Persists into
    BENCH_serving.json."""
    import jax
    from repro.configs.base import get_config
    from repro.core.elastic import ElasticServing
    from repro.data.pipeline import Request
    from repro.models import model_api as MA
    from repro.streaming.runtime import DecodeRuntime, RuntimeConfig

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    n_req = 32 if FAST else 96

    def request_set():
        # short-heavy, max_new varied — against a slab *provisioned* for
        # 256-token prompts + 256 generated (the serving posture: admit up
        # to the configured max, observe mostly short). The dense slab
        # pays its provisioned capacity per decode step; paged pays only
        # what is live.
        rng = np.random.default_rng(11)
        reqs = []
        for i in range(n_req):
            plen = int(rng.integers(4, 31))
            mnew = int(rng.integers(2, 33))
            reqs.append(Request(i + 1, 0.0, plen, mnew))
        return reqs

    shape = dict(max_batch=8, max_prompt_bucket=256, max_new_cap=256)
    dense_cfg = RuntimeConfig(paged=False, block_skip=0, **shape)
    dense_entries = (dense_cfg.max_batch + 1) * dense_cfg.capacity
    pool = dense_entries // 32                 # equal-HBM page budget
    variants = {
        "dense": dense_cfg,
        "dense_skip": RuntimeConfig(paged=False, **shape),
        "paged": RuntimeConfig(paged=True, page_size=32, **shape),
        "paged_wide": RuntimeConfig(paged=True, page_size=32,
                                    pool_pages=pool,
                                    **dict(shape, max_batch=24)),
    }
    tokens = sum(r.max_new for r in request_set())

    def run_variant(rcfg):
        rt = DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                           gen=serving.build_gen)

        def one_pass():
            rt.submit(request_set())
            t0 = time.perf_counter()
            done = rt.pump()
            assert len(done) == n_req
            return time.perf_counter() - t0

        cold = one_pass()
        warm = min(one_pass() for _ in range(5 if FAST else 3))
        out = {"cold_s": round(cold, 4), "s": round(warm, 4),
               "tok_per_s": round(tokens / warm, 1),
               "traces": dict(rt.kernels.trace_counts),
               "trace_bound": rt.kernels.max_traces}
        if rcfg.paged:
            out["pages_hwm"] = rt.pages_hwm
            out["kv_entries"] = rt.alloc.n_pages * rcfg.page_size
        else:
            out["kv_entries"] = dense_entries
        return out

    res = {k: run_variant(v) for k, v in variants.items()}
    speedup = res["dense"]["s"] / res["paged_wide"]["s"]
    same_slots = res["dense"]["s"] / res["paged"]["s"]
    skip_only = res["dense"]["s"] / res["dense_skip"]["s"]
    report = {"name": "paged_decode", "arch": f"{cfg.name}.reduced",
              "requests": n_req, "useful_tokens": tokens, "fast": FAST,
              **res, "speedup": round(speedup, 2),
              "same_slot_speedup": round(same_slots, 2),
              "block_skip_speedup": round(skip_only, 2)}
    write_serving("paged_decode", report)
    row("paged_decode", res["paged_wide"]["s"] * 1e6,
        f"dense_tok_per_s={res['dense']['tok_per_s']};"
        f"dense_skip_tok_per_s={res['dense_skip']['tok_per_s']};"
        f"paged_tok_per_s={res['paged']['tok_per_s']};"
        f"paged_wide_tok_per_s={res['paged_wide']['tok_per_s']};"
        f"speedup={speedup:.2f};same_slot_speedup={same_slots:.2f};"
        f"block_skip_speedup={skip_only:.2f};"
        f"pages_hwm={res['paged_wide']['pages_hwm']}")


def bench_prefix_reuse():
    """Prefix-sharing admission + multi-token speculative decode vs the
    PR-4 paged baseline, two phases on one model build:

    Phase 1 (admission): an 80%-shared request mix — four prompt template
    groups plus 20% unique prompts — against a warm prefix cache (one
    long-lived paver per group holds the interned pages live, the serving
    posture for system-prompt traffic). With ``prefix_cache`` on, every
    grouped admission is a splice (host page-table write + refcount++ +
    one device stamp, zero prefill FLOPs) and only the unique 20% prefill;
    off is PR-4: every admission prefills its full prompt.
    ``admit_speedup`` times the admission dispatch sequence alone (the
    slab holds the whole mix, so no decode blocks ride along);
    ``pages_hwm`` drops because grouped rows share their prompt pages.

    Phase 2 (speculative decode): replay traffic — one paver streams a
    prompt to completion, then a batch of identical requests is served
    again (greedy decode is deterministic, so the drafter replays the
    paver's stream near-perfectly). ``spec_speedup`` = k-token verify
    dispatches (spec_decode=k) vs the ISSUE baseline of one token per
    dispatch (decode_block=1). Tokens are byte-identical either way —
    the accept-prefix rule only changes dispatch count, never content.

    Both phases warm up with the *same request ids* they then measure:
    identical content => identical acceptance trajectories => identical
    dispatch shapes, so the measured pass is fully trace-cached.
    Persists into BENCH_serving.json; --check floors admit_speedup and
    spec_speedup."""
    import jax
    from repro.configs.base import get_config
    from repro.core.elastic import ElasticServing
    from repro.data.pipeline import Request
    from repro.kernels import ops as OPS
    from repro.models import model_api as MA
    from repro.streaming.runtime import DecodeRuntime, RuntimeConfig

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    n_req = 40 if FAST else 80
    plen, n_groups = 64, 4
    plen_a = 128                  # admission-phase prompts (prefill-heavy)

    def admit_set():
        # 80% of requests carry a template group's full prompt; i%5==0
        # stays unique. max_new=2 keeps the phase admission-dominated.
        return [Request(i + 1, 0.0, plen_a, 2,
                        prefix_group=0 if i % 5 == 0 else i % n_groups + 1,
                        prefix_len=0 if i % 5 == 0 else plen_a)
                for i in range(n_req)]

    def pavers():
        # one long-lived holder per template keeps its interned prompt
        # pages referenced (and so cached) across the measured admission
        return [Request(10_000 + g, 0.0, plen_a, 24,
                        prefix_group=g, prefix_len=plen_a)
                for g in range(1, n_groups + 1)]

    def run_admit(prefix_cache):
        # slab sized to hold the whole mix at once: the timed region is
        # the admission dispatch sequence alone (prefill vs splice), no
        # decode blocks riding in the measurement
        rcfg = RuntimeConfig(paged=True, page_size=16,
                             max_batch=n_req + n_groups,
                             max_prompt_bucket=plen_a,
                             admit_tail=0, prefix_cache=prefix_cache)
        rt = DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                           gen=serving.build_gen)

        def one_pass():
            rt.submit(pavers())
            rt.step()                      # admit the template holders
            rt.submit(admit_set())
            t0 = time.perf_counter()
            rt._admit_some()
            dt = time.perf_counter() - t0
            assert not rt.pending and rt.inflight == n_req + n_groups
            while rt.inflight:             # drain everything untimed
                rt.step()
            return dt

        cold = one_pass()
        warm = min(one_pass() for _ in range(5 if FAST else 3))
        return {"cold_s": round(cold, 4), "s": round(warm, 4),
                "admit_tok_per_s": round(n_req * plen_a / warm, 1),
                "pages_hwm": rt.pages_hwm,
                "prefix_hits": rt.prefix_hits,
                "prefix_lookups": rt.prefix_lookups,
                "traces": dict(rt.kernels.trace_counts),
                "trace_bound": rt.kernels.max_traces}

    def run_spec(k):
        rcfg = RuntimeConfig(paged=True, page_size=16, max_batch=8,
                             admit_tail=0, spec_decode=k,
                             decode_block=1 if k == 0 else 16)
        rt = DecodeRuntime(serving.runtime_kernels(rcfg), serving.params,
                           gen=serving.build_gen)
        dep = 32 if FAST else 64

        def replay(rid0):
            return [Request(rid0 + j, 0.0, plen, dep,
                            prefix_group=1, prefix_len=plen)
                    for j in range(8)]

        rt.submit([Request(1, 0.0, plen, dep,
                           prefix_group=1, prefix_len=plen)])
        rt.pump()                          # pave the stream (untimed)
        rt.submit(replay(2))
        rt.pump()                          # warm: same rids as measured
        rt.submit(replay(2))
        t0 = time.perf_counter()
        done = rt.pump()
        dt = time.perf_counter() - t0
        assert len(done) == 8
        out = {"s": round(dt, 4),
               "tok_per_s": round(8 * dep / dt, 1),
               "traces": dict(rt.kernels.trace_counts)}
        if k:
            out["accept_rate"] = round(rt.spec_accept_rate, 3)
            out["rounds"] = rt.spec_rounds
        return out

    on, off = run_admit(True), run_admit(False)
    admit_speedup = off["s"] / on["s"]
    spec, base = run_spec(3), run_spec(0)
    spec_speedup = base["s"] / spec["s"]
    report = {"name": "prefix_reuse", "arch": f"{cfg.name}.reduced",
              "requests": n_req, "fast": FAST,
              "kernel_mode": OPS.resolved_mode(),
              "prefix_on": on, "prefix_off": off,
              "admit_speedup": round(admit_speedup, 2),
              "spec_k3": spec, "one_token": base,
              "spec_speedup": round(spec_speedup, 2)}
    write_serving("prefix_reuse", report)
    row("prefix_reuse", on["s"] * 1e6,
        f"admit_speedup={admit_speedup:.2f};"
        f"admit_tok_per_s={on['admit_tok_per_s']};"
        f"baseline_admit_tok_per_s={off['admit_tok_per_s']};"
        f"hit_rate={on['prefix_hits'] / max(on['prefix_lookups'], 1):.2f};"
        f"pages_hwm={on['pages_hwm']};baseline_pages_hwm={off['pages_hwm']};"
        f"spec_speedup={spec_speedup:.2f};"
        f"spec_accept_rate={spec['accept_rate']};"
        f"kernel_mode={report['kernel_mode']}")


# ---------------------------------------------------------------- kernels

def bench_kernel_flash_attention():
    import jax
    import jax.numpy as jnp
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.ref import attention_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 256, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 256, 64), jnp.float32)
    ref = jax.jit(lambda q, k, v: attention_ref(q, k, v))
    us_ref = _timeit(lambda: jax.block_until_ready(ref(q, k, v)), n=20)
    out_k = flash_attention(q, k, v, interpret=True)
    err = float(jnp.max(jnp.abs(out_k - ref(q, k, v))))
    row("kernel_flash_attention", us_ref,
        f"jnp_oracle_us={us_ref:.0f};interpret_allclose_err={err:.1e}")


def bench_kernel_mlstm():
    import jax
    import jax.numpy as jnp
    from repro.models.xlstm import mlstm_chunkwise
    from repro.kernels.mlstm_scan import mlstm_chunkwise_kernel
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    B, S, H, dh = 1, 256, 2, 64
    q = jax.random.normal(ks[0], (B, S, H, dh))
    k = jax.random.normal(ks[1], (B, S, H, dh)) * dh ** -0.5
    v = jax.random.normal(ks[2], (B, S, H, dh))
    li = jax.random.normal(ks[3], (B, S, H))
    lf = jax.random.normal(ks[4], (B, S, H))
    jnp_fn = jax.jit(lambda *a: mlstm_chunkwise(*a)[0])
    us = _timeit(lambda: jax.block_until_ready(jnp_fn(q, k, v, li, lf)), n=20)
    hk, _ = mlstm_chunkwise_kernel(q, k, v, li, lf, interpret=True)
    err = float(jnp.max(jnp.abs(hk - jnp_fn(q, k, v, li, lf))))
    row("kernel_mlstm_chunkwise", us,
        f"jnp_chunkwise_us={us:.0f};interpret_allclose_err={err:.1e}")


def bench_kernel_ssm():
    import jax
    import jax.numpy as jnp
    from repro.kernels.ref import ssm_ref
    from repro.kernels.ssm_scan import ssm_scan_kernel
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    B, S, di, N = 1, 256, 256, 16
    u = jax.random.normal(ks[0], (B, S, di))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, N)))
    Bs = jax.random.normal(ks[3], (B, S, N))
    Cs = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (di,))
    ref = jax.jit(lambda *a: ssm_ref(*a)[0])
    us = _timeit(lambda: jax.block_until_ready(ref(u, dt, A, Bs, Cs, D)), n=10)
    yk, _ = ssm_scan_kernel(u, dt, A, Bs, Cs, D, interpret=True)
    err = float(jnp.max(jnp.abs(yk - ref(u, dt, A, Bs, Cs, D))))
    row("kernel_ssm_scan", us,
        f"jnp_oracle_us={us:.0f};interpret_allclose_err={err:.1e}")


def bench_kernel_decode_attention():
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention_kernel
    from repro.kernels.ref import decode_attention_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (4, 8, 64))
    kc = jax.random.normal(ks[1], (4, 1024, 2, 64))
    vc = jax.random.normal(ks[2], (4, 1024, 2, 64))
    lens = jnp.asarray([100, 512, 900, 1024], jnp.int32)
    ref = jax.jit(lambda q, k, v, l: decode_attention_ref(q, k, v, lengths=l))
    us = _timeit(lambda: jax.block_until_ready(ref(q, kc, vc, lens)), n=20)
    ok = decode_attention_kernel(q, kc, vc, lens, interpret=True)
    err = float(jnp.max(jnp.abs(ok - ref(q, kc, vc, lens))))
    row("kernel_decode_attention", us,
        f"jnp_oracle_us={us:.0f};interpret_allclose_err={err:.1e}")


# ----------------------------------------------------------------- roofline

def bench_roofline():
    """Summarize dry-run roofline artifacts. The dry-run is its own
    process (``python -m repro.launch.dryrun``, pre-jax device-count flag)
    and its artifacts are not committed — so when none exist this row says
    *why* it carries no signal instead of reporting a misleading
    ``cells_ok=0``."""
    base = pathlib.Path(__file__).resolve().parents[1] / "experiments" / "dryrun"
    cells = [f for mesh in ("pod", "multipod")
             for f in sorted((base / mesh).glob("*.json"))
             if (base / mesh).exists()]
    if not cells:
        row("roofline_dryrun_summary", 0.0,
            "status=skipped;reason=no dryrun artifacts under "
            "experiments/dryrun (generate: python -m repro.launch.dryrun"
            " --all)")
        return
    n_ok, n_err, worst = 0, 0, None
    for f in cells:
        r = json.loads(f.read_text())
        if r.get("status") != "ok":
            n_err += 1
            continue
        n_ok += 1
        frac = r.get("useful_flops_ratio", 0.0)
        if f.parent.name == "pod" and (worst is None or frac < worst[1]):
            worst = (f"{r['arch']}x{r['shape']}", frac)
    from repro.kernels import ops as OPS
    derived = f"status=ok;cells_ok={n_ok};cells_err={n_err}"
    if worst:
        derived += f";worst_useful_flops={worst[0]}:{worst[1]:.3f}"
    # self-describing record: which kernel dispatch produced these numbers
    derived += f";kernel_mode={OPS.resolved_mode()}"
    row("roofline_dryrun_summary", 0.0, derived)


def bench_observability_overhead():
    """Observability cost floor: steady-state tokens/s with the full
    plane wired (lifecycle Tracer + FlightRecorder with burn-rate
    checks + TickProfiler) vs stock, same engine configuration and
    manufactured request set. Tracing must be cheap enough to leave on:
    ``--check`` floors ``tokens_ratio`` (on/off) at 0.95, i.e. <5%
    throughput cost. The on-path report carries the tick phase
    breakdown so BENCH_serving.json records where the tick goes."""
    import jax
    from repro.configs.base import get_config
    from repro.core.elastic import ElasticServing
    from repro.core.jrm import SliceSpec, start_vk
    from repro.core.observability import FlightRecorder, SLOConfig, \
        TickProfiler
    from repro.core.tracing import Tracer
    from repro.data.pipeline import RequestSource
    from repro.models import model_api as MA
    from repro.streaming.engine import StreamEngine

    cfg = get_config("qwen2-7b").reduced()
    serving = ElasticServing(cfg, tp=1).build(1, seed=0)
    n_req = 24 if FAST else 96

    def request_set():
        src = RequestSource(seed=11, prompt_range=(8, 48),
                            max_new_range=(2, 32))
        return src.arrivals(0.0, 1.0, lam=float(n_req))

    def mk_engine(tag, obs):
        nodes = [start_vk(f"obs-{tag}", now=0.0,
                          slice_spec=SliceSpec(chips=4))]
        eng = StreamEngine(cfg, serving, nodes, service_rate=1e9,
                           max_batch=8, use_runtime=True)
        eng.deploy(0.0)
        if obs:
            tracer = Tracer()
            # a finite (never-tripping) SLO so check() pays the real
            # burn-rate evaluation cost every tick
            eng.enable_observability(
                tracer=tracer,
                recorder=FlightRecorder(tracer, slo=SLOConfig(lc_p99_s=1e9)),
                profiler=TickProfiler())
        return eng

    def one_pass(eng, now):
        eng.queue.extend(request_set())
        t0 = time.perf_counter()
        eng.tick(now, 1.0, lam=0.0)
        if eng.recorder is not None:
            eng.recorder.check(now)
        return time.perf_counter() - t0

    # interleaved min-of-many warm passes: the contrast is a few
    # percent, so a sustained noise window hitting only one path's
    # measurement run would swamp the signal the 0.95 --check floor
    # guards — alternating passes exposes both engines to the same
    # ambient conditions, and min-of-N converges each to its floor
    eng_off = mk_engine("off", False)
    eng_on = mk_engine("on", True)
    n_pass = 13 if FAST else 7
    cold = {"off": one_pass(eng_off, 0.0), "on": one_pass(eng_on, 0.0)}
    warm = {"off": math.inf, "on": math.inf}
    for t in range(1, n_pass):
        warm["off"] = min(warm["off"], one_pass(eng_off, float(t)))
        warm["on"] = min(warm["on"], one_pass(eng_on, float(t)))
    tokens = sum(r.max_new for r in request_set())

    def path_report(key, eng):
        out = {"cold_s": round(cold[key], 4), "s": round(warm[key], 4),
               "tok_per_s": round(tokens / warm[key], 1)}
        if eng.tracer is not None:
            assert eng.tracer.spans, "observability on but no spans"
            out["spans"] = len(eng.tracer.spans)
            out["profile"] = eng.profiler.summary()
        return out

    off = path_report("off", eng_off)
    on = path_report("on", eng_on)
    ratio = on["tok_per_s"] / off["tok_per_s"]
    report = {"name": "observability_overhead",
              "arch": f"{cfg.name}.reduced", "requests": n_req,
              "fast": FAST, "off": off, "on": on,
              "tokens_ratio": round(ratio, 3)}
    write_serving("observability_overhead", report)
    row("observability_overhead", on["s"] * 1e6,
        f"tokens_ratio={ratio:.3f};on_tok_per_s={on['tok_per_s']};"
        f"off_tok_per_s={off['tok_per_s']};spans={on['spans']}")


BENCHES = [
    bench_lifecycle_create, bench_lifecycle_monitor,
    bench_hpa_formula, bench_hpa_scaling,
    bench_queue_16, bench_queue_32,
    bench_dbn_tracking, bench_dbn_control,
    bench_deployment_40, bench_control_plane_churn, bench_federation_churn,
    bench_priority_spike, bench_chaos_soak, bench_overload_brownout,
    bench_scale_bringup,
    bench_serving_throughput, bench_paged_decode, bench_prefix_reuse,
    bench_observability_overhead,
    bench_kernel_flash_attention, bench_kernel_mlstm, bench_kernel_ssm,
    bench_kernel_decode_attention,
    bench_roofline,
]

# ratio metrics guarded by --check: machine-independent speedups measured
# within one process, so a CI runner's absolute speed does not matter.
# key -> (report name in BENCH_serving.json, metric field, description)
CHECK_METRICS = {
    "serving_throughput": ("serving_throughput", "speedup",
                           "slot-slab runtime vs chunked path"),
    "paged_decode": ("paged_decode", "speedup",
                     "paged KV slab vs dense slab (equal HBM)"),
    "prefix_admit": ("prefix_reuse", "admit_speedup",
                     "prefix-cache admission vs PR-4 paged admission"),
    "spec_decode": ("prefix_reuse", "spec_speedup",
                    "k-token speculative decode vs 1-token-per-dispatch"),
    "observability": ("observability_overhead", "tokens_ratio",
                      "full tracing/recorder/profiler plane on vs off"),
}


def _check_ratios(report):
    return {key: report[rkey][metric] for key, (rkey, metric, _) in
            CHECK_METRICS.items() if rkey in report}


def run_check(tol: float, record: bool) -> int:
    """Benchmark regression guard (CI: ``benchmarks/run.py --check``).

    Re-runs the serving benches in fast-smoke mode and compares their
    speedup ratios against the ``fast_baseline`` stanza committed in
    BENCH_serving.json; a ratio more than ``tol`` below baseline fails the
    job instead of silently uploading worse numbers. Also enforces the
    semantic floors (runtime beats chunked; paged clearly beats dense —
    the full >=1.5x claim lives in the committed full-run numbers) and
    the jit trace bound, and fast-smokes ``bench_priority_spike``,
    ``bench_chaos_soak``, ``bench_overload_brownout`` and
    ``bench_scale_bringup``, whose internal assertions (zero serving
    loss, bounded p99, exactly-once chaos recovery, zero
    latency-critical SLO violations under overload with bounded shed
    fraction, scale-floor throughput) fail the job directly. Noise posture on shared runners: the recorded
    baseline is the *min* of two smoke runs (the slowest healthy
    observation) while enforcement takes the *best* of up to two runs, so
    only a genuine regression trips the ``tol`` gap. ``record=True``
    refreshes the baseline stanza in-place (run after a deliberate perf
    change, commit the JSON)."""
    global FAST, JSON_DIR
    path = ROOT / "BENCH_serving.json"
    committed = json.loads(path.read_text()) if path.exists() else {}
    FAST = True
    if JSON_DIR == ROOT:
        # never clobber the committed full-run JSONs with smoke numbers —
        # the fresh fast report lands next to them instead
        JSON_DIR = ROOT / "bench_check"
        JSON_DIR.mkdir(exist_ok=True)
    # assertion-based gates first (cheap, no ratio to baseline): QoS
    # invariants, then the chaos soak's robustness floor (zero loss,
    # exactly-once, token-identical recovery, bounded recovery latency)
    bench_priority_spike()
    bench_chaos_soak()
    bench_overload_brownout()
    bench_scale_bringup()

    def smoke():
        bench_serving_throughput()
        bench_paged_decode()
        bench_prefix_reuse()
        bench_observability_overhead()
        return json.loads((JSON_DIR / "BENCH_serving.json").read_text())

    def evaluate(ratios, baseline):
        failures = []
        if ratios.get("serving_throughput", 0.0) <= 1.0:
            failures.append("slot-slab runtime slower than the chunked path")
        if ratios.get("paged_decode", 0.0) < 1.2:
            failures.append(f"paged decode speedup "
                            f"{ratios.get('paged_decode')} < 1.2x smoke floor")
        if ratios.get("prefix_admit", 0.0) < 3.0:
            failures.append(f"prefix-cache admission speedup "
                            f"{ratios.get('prefix_admit')} < 3.0x floor")
        if ratios.get("spec_decode", 0.0) < 1.3:
            failures.append(f"speculative decode speedup "
                            f"{ratios.get('spec_decode')} < 1.3x floor")
        if ratios.get("observability", 0.0) < 0.95:
            failures.append(f"observability plane costs >5% tokens/s "
                            f"(on/off ratio "
                            f"{ratios.get('observability')} < 0.95)")
        for key, got in sorted(ratios.items()):
            base = baseline.get(key)
            if base is not None and (base - got) / base > tol:
                failures.append(
                    f"{key}: speedup {got} regressed >"
                    f"{tol * 100:.0f}% from committed baseline {base} "
                    f"({CHECK_METRICS[key][2]})")
        return failures

    fresh = smoke()
    rt = fresh["serving_throughput"]["runtime"]
    trace_fail = ([f"jit trace count {rt['traces']} exceeds bound "
                   f"{rt['trace_bound']}"]
                  if rt["traces"]["admit"] + rt["traces"]["decode"]
                  > rt["trace_bound"] else [])
    ratios = _check_ratios(fresh)
    if record:
        second = _check_ratios(smoke())
        ratios = {k: round(min(v, second.get(k, v)), 2)
                  for k, v in ratios.items()}
        committed = committed or fresh
        committed["fast_baseline"] = ratios
        path.write_text(json.dumps(committed, indent=2) + "\n")
        print(f"[check] recorded fast_baseline={ratios} "
              f"(min of two smoke runs)")
    baseline = committed.get("fast_baseline", {})
    failures = evaluate(ratios, baseline)
    if failures and not record:
        print(f"[check] first run failed ({len(failures)} finding(s)) — "
              f"retrying once against smoke noise")
        second = _check_ratios(smoke())
        ratios = {k: max(v, second.get(k, v)) for k, v in ratios.items()}
        failures = evaluate(ratios, baseline)
    failures = trace_fail + failures
    for key, got in sorted(ratios.items()):
        base = baseline.get(key)
        verdict = ("no-baseline" if base is None else
                   f"baseline={base} drop={(base - got) / base * 100:+.0f}%")
        print(f"[check] {key}: speedup={got} ({verdict})")
    for f in failures:
        print(f"[check] FAIL: {f}")
    if not failures:
        print("[check] OK")
    return 1 if failures else 0


def main(argv=None) -> int:
    global FAST, JSON_DIR
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="run only benches whose name contains this")
    ap.add_argument("--fast", action="store_true",
                    help="shrink expensive workloads (CI smoke)")
    ap.add_argument("--json-dir", default=str(ROOT))
    ap.add_argument("--check", action="store_true",
                    help="fast-smoke the serving benches and fail on a"
                         " throughput regression vs the committed"
                         " BENCH_serving.json baselines")
    ap.add_argument("--check-tol", type=float, default=0.25,
                    help="allowed fractional speedup regression in --check")
    ap.add_argument("--record-check-baseline", action="store_true",
                    help="with --check: refresh the committed"
                         " fast_baseline stanza instead of enforcing it")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    FAST = args.fast
    JSON_DIR = pathlib.Path(args.json_dir)
    JSON_DIR.mkdir(parents=True, exist_ok=True)
    if args.check:
        return run_check(args.check_tol, args.record_check_baseline)
    print("name,us_per_call,derived")
    for b in BENCHES:
        if args.only and args.only not in b.__name__:
            continue
        n0 = len(RESULTS)
        t0 = time.perf_counter()
        b()
        # stamp the bench's wall-clock (setup + all passes) on every row
        # it emitted, so BENCH_run.json tracks where the suite's time goes
        wall = round(time.perf_counter() - t0, 3)
        for r in RESULTS[n0:]:
            r["wall_s"] = wall
    (JSON_DIR / "BENCH_run.json").write_text(
        json.dumps(RESULTS, indent=2) + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
