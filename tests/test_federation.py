"""Multi-site federation: per-site pools + SiteView aggregates, site
filter/score stages (selector, anti-affinity, data locality,
latency-weighted spreading), batch drain of a whole pilot allocation with
zero request loss, and JCS proactive re-provisioning on walltime
shortfall."""
import pytest

from repro.configs.base import get_config
from repro.core.cluster import Cluster, Deployment, PodTemplate
from repro.core.controllers import ControlPlane
from repro.core.elastic import ElasticServing
from repro.core.jcs import CentralService
from repro.core.jfe import FrontEnd
from repro.core.jrm import SliceSpec, start_vk
from repro.core.scheduler import Scheduler, SiteTopology
from repro.core.state_machine import Container, Pod
from repro.streaming.engine import StreamEngine

TOL = [{"key": "virtual-kubelet.io/provider", "value": "mock"}]


def mkpod(name="p", chips=1, hbm=0):
    return Pod(name, [Container("c")], tolerations=list(TOL),
               request_chips=chips, request_hbm_bytes=hbm)


def mkcluster(site_nodes, chips=4, walltime=0.0, now=0.0):
    """site_nodes: {site: n_nodes}; node names are <site><i>."""
    cluster = Cluster()
    for site, n in site_nodes.items():
        for i in range(n):
            cluster.register_node(
                start_vk(f"{site}{i}", site=site, walltime=walltime, now=now,
                         slice_spec=SliceSpec(chips=chips)), now)
            cluster.heartbeat(f"{site}{i}", now)
    return cluster


# ---------------------------------------------------------- site views

def test_site_views_aggregate_capacity_and_runway():
    cluster = mkcluster({"jlab": 2, "nersc": 1}, chips=4, walltime=300.0)
    views = cluster.site_views(0.0)
    assert set(views) == {"jlab", "nersc"}
    v = views["jlab"]
    assert v.nodes == 2 and v.ready_nodes == 2
    assert v.total_chips == 8 and v.free_chips == 8
    # runway = sum of (alive_left - drain_margin) = 2 * (300 - 60)
    assert v.remaining_walltime == pytest.approx(480.0)
    assert v.min_walltime == pytest.approx(300.0)
    # a bound pod consumes site capacity
    cluster.submit(mkpod("a", chips=3), 1.0)
    Scheduler(cluster).run_once(1.0)
    views = cluster.site_views(1.0)
    assert views["jlab"].free_chips + views["nersc"].free_chips == 9
    # infinite-lease sites report infinite runway
    infinite = mkcluster({"local": 1}, walltime=0.0)
    assert infinite.site_view("local", 0.0).remaining_walltime == float("inf")


def test_site_view_counts_draining_nodes():
    cluster = mkcluster({"jlab": 2}, walltime=100.0)
    view = cluster.site_view("jlab", 50.0)   # alive_left=50 < 60s margin
    assert view.draining_nodes == 2


# ------------------------------------------------------- filter stages

def test_site_selector_and_anti_affinity():
    cluster = mkcluster({"jlab": 1, "nersc": 1})
    sched = Scheduler(cluster)
    cluster.submit(mkpod("pinned"), 0.0, site_selector=("nersc",))
    cluster.submit(mkpod("averse"), 0.0, site_anti_affinity=("nersc",))
    sched.run_once(0.0)
    assert cluster.pods["pinned"].pod.node == "nersc0"
    assert cluster.pods["averse"].pod.node == "jlab0"
    # no site satisfies the selector -> FailedScheduling with a site reason
    rec = cluster.submit(mkpod("nowhere"), 0.0, site_selector=("ornl",))
    decisions = sched.run_once(0.0)
    assert decisions[-1].node is None and "site" in rec.last_reason


def test_preemption_requeue_keeps_site_spec():
    cluster = mkcluster({"jlab": 1, "nersc": 1}, chips=2)
    sched = Scheduler(cluster)
    cluster.submit(mkpod("low", chips=2), 0.0, priority=0,
                   site_selector=("jlab",))
    sched.run_once(0.0)
    cluster.submit(mkpod("high", chips=2), 1.0, priority=10,
                   site_selector=("jlab",))
    sched.run_once(1.0)
    assert cluster.pods["high"].pod.node == "jlab0"
    victim = cluster.pods["low"]
    assert not victim.bound
    assert victim.site_selector == ("jlab",)   # spec survives the requeue
    sched.run_once(2.0)                        # nersc is free but off-limits
    assert not victim.bound


# -------------------------------------------------------- score stages

def test_data_locality_pins_to_stream_home_site():
    cluster = mkcluster({"jlab": 1, "nersc": 1})
    topo = SiteTopology(data_sites={"ejfat": "nersc"}).connect(
        "jlab", "nersc", 40.0)
    sched = Scheduler(cluster, topology=topo)
    # control: without a data stream the tie breaks to the first node
    cluster.submit(mkpod("free"), 0.0)
    # pinned: the ejfat stream lives at nersc -> locality dominates
    cluster.submit(mkpod("pinned"), 0.0, data_stream="ejfat")
    sched.run_once(0.0)
    assert cluster.pods["free"].pod.node == "jlab0"
    assert cluster.pods["pinned"].pod.node == "nersc0"


def test_latency_weighted_cross_site_spread():
    """Owner's first replica lands at jlab; jlab then fills up, and the
    spillover replica picks the *nearest* other site by the latency
    matrix (nersc at 10ms over ornl at 100ms)."""
    cluster = mkcluster({"jlab": 1, "nersc": 1, "ornl": 1}, chips=2)
    topo = (SiteTopology().connect("jlab", "nersc", 10.0)
            .connect("jlab", "ornl", 100.0).connect("nersc", "ornl", 50.0))
    sched = Scheduler(cluster, topology=topo)
    cluster.submit(mkpod("r0", chips=2), 0.0, owner="app")
    sched.run_once(0.0)
    assert cluster.pods["r0"].pod.node == "jlab0"
    cluster.submit(mkpod("r1", chips=2), 1.0, owner="app")
    sched.run_once(1.0)
    assert cluster.pods["r1"].pod.node == "nersc0"


def test_site_spread_beats_bestfit():
    """Replicas of one owner spread across sites even when the already-
    used site would be the tighter HBM fit."""
    cluster = Cluster()
    cluster.register_node(start_vk("jlab0", site="jlab", slice_spec=SliceSpec(
        chips=8, hbm_bytes_per_chip=1 << 30)), 0.0)
    cluster.register_node(start_vk("nersc0", site="nersc", slice_spec=SliceSpec(
        chips=8, hbm_bytes_per_chip=8 << 30)), 0.0)
    for name in cluster.nodes:
        cluster.heartbeat(name, 0.0)
    sched = Scheduler(cluster)
    for i in range(2):
        cluster.submit(mkpod(f"r{i}", chips=1, hbm=1 << 30), 0.0, owner="app")
    sched.run_once(0.0)
    sites = {cluster.nodes[cluster.pods[f"r{i}"].pod.node].site
             for i in range(2)}
    assert sites == {"jlab", "nersc"}


def test_topology_parse():
    topo = SiteTopology.parse("jlab:nersc:40,nersc:ornl:18", "ejfat=jlab")
    assert topo.latency("nersc", "jlab") == 40.0     # symmetric
    assert topo.latency("jlab", "jlab") == 0.0
    assert topo.latency("jlab", "ornl") == topo.default_latency_ms
    assert topo.data_sites == {"ejfat": "jlab"}


# ------------------------------------------- multi-facility workflows

def test_multi_site_workflow_targeting():
    fe = FrontEnd()
    jcs = CentralService(fe)
    cluster = Cluster()
    wfs = fe.add_multi_wf("vk-", {"jlab": 2, "nersc": 3}, nodetype="tpu",
                          walltime=600.0)
    assert len(wfs) == 2 and len({wf.group for wf in wfs}) == 1
    assert fe.group_wfs(wfs[0].group) == wfs
    pilots = jcs.launch_multi(wfs, now=0.0, cluster=cluster)
    assert len(pilots) == 2
    assert all(wf.state == "RUNNING" for wf in wfs)
    assert len(cluster.site_nodes("jlab")) == 2
    assert len(cluster.site_nodes("nersc")) == 3
    assert all(n.nodetype == "tpu" for n in cluster.nodes.values())


# ------------------------------------------- proactive re-provisioning

def test_jcs_reprovision_on_walltime_shortfall():
    """A site whose aggregate runway no longer covers its pods' remaining
    work gets a fresh pilot (sized by the shortfall, capped at 1:1 node
    replacement) *before* the drain wave; sites with enough runway are
    untouched; the top-up makes the next call a no-op."""
    fe = FrontEnd()
    jcs = CentralService(fe)
    cluster = mkcluster({"nersc": 2}, chips=4, walltime=300.0)
    # an infinite-lease site never triggers re-provisioning
    cluster.register_node(start_vk("local0", site="local"), 0.0)
    cluster.heartbeat("local0", 0.0)
    # two pods at nersc owing 600s each: demand 1200 > runway 480
    for i in range(2):
        cluster.submit(mkpod(f"w{i}", chips=1), 0.0, expected_duration=600.0)
        cluster.assign(f"w{i}", f"nersc{i}", 0.0)
    pilots = jcs.reprovision(cluster, 0.0, horizon=600.0, walltime=3600.0)
    assert len(pilots) == 1
    new = [n for n in cluster.site_nodes("nersc") if n.name not in
           ("nersc0", "nersc1")]
    # one 3600s lease covers the 720s shortfall (capped at the 2 expiring)
    assert len(new) == 1
    assert all(n.walltime == pytest.approx(3540.0) for n in new)
    assert all(n.slice_spec.chips == 4 for n in new)
    wf = fe.table[pilots[0].wf_id]
    assert wf.site == "nersc" and wf.state == "RUNNING"
    # supply now covers demand -> self-limiting
    assert jcs.reprovision(cluster, 1.0, horizon=600.0) == []
    # the scheduler can immediately use the fresh lease for long work
    # (the original nersc nodes' 240s runway could never hold 2000s)
    rec = cluster.submit(mkpod("long", chips=1), 5.0,
                         expected_duration=2000.0, site_selector=("nersc",))
    Scheduler(cluster).run_once(5.0)
    assert rec.bound and cluster.nodes[rec.pod.node] in new


def test_jcs_reprovision_sizes_from_starved_chip_concurrency():
    """PR-3 follow-up: pilots are also sized by the chip demand of
    capacity-starved pending pods — including fragmentation (aggregate
    free chips cannot host a pod no single node fits) — while
    quota-blocked pods never trigger one (a fair-share cap is not
    helped by more nodes, even though its reject message names chips)."""
    from repro.core.qos import Quota
    fe = FrontEnd()
    jcs = CentralService(fe)
    # open-ended leases: walltime shortfall is never the trigger here
    cluster = mkcluster({"nersc": 2}, chips=2, walltime=0.0)
    sched = Scheduler(cluster)
    # fragment the pool: one 1-chip pod per node leaves 1+1 free chips
    for i in range(2):
        cluster.submit(mkpod(f"frag{i}", chips=1), 0.0)
    sched.run_once(0.0)
    # a quota-blocked pod alone must not provision anything
    cluster.apply_quota(Quota(owner="capped", chips=0), 1.0)
    cluster.submit(mkpod("q0", chips=1), 1.0, owner="capped")
    sched.run_once(1.0)
    assert "quota" in cluster.pods["q0"].last_reason
    assert jcs.reprovision(cluster, 2.0, horizon=600.0) == []
    # a 2-chip pod fits neither node (2 free chips in aggregate, 1+1
    # fragmented) -> the chip-concurrency path launches a pilot
    big = cluster.submit(mkpod("big", chips=2), 3.0)
    sched.run_once(3.0)
    assert not big.bound and "chips" in big.last_reason
    pilots = jcs.reprovision(cluster, 4.0, horizon=600.0, walltime=3600.0)
    assert len(pilots) == 1 and len(pilots[0].nodes) == 1
    cluster.heartbeat(pilots[0].nodes[0], 4.0)
    sched.run_once(4.0 + sched.backoff_max)
    assert big.bound and big.pod.node == pilots[0].nodes[0]
    # self-limiting: demand met, next call is a no-op
    assert jcs.reprovision(cluster, 5.0 + sched.backoff_max,
                           horizon=600.0) == []
    # a pod no replacement node could host either (request > slice size)
    # must never trigger pilots — launching would repeat forever
    huge = cluster.submit(mkpod("huge", chips=5), 100.0)
    sched.run_once(100.0)
    assert not huge.bound
    assert jcs.reprovision(cluster, 101.0, horizon=600.0) == []
    assert jcs.reprovision(cluster, 102.0, horizon=600.0) == []


def test_jcs_reprovision_counts_queue_backlog():
    """Live queue backlog converts to pod-seconds of serving demand: a
    site whose runway covers its pods' declared durations still gets a
    pilot when the backlog says the fleet is behind."""
    fe = FrontEnd()
    jcs = CentralService(fe)
    cluster = mkcluster({"nersc": 1}, chips=4, walltime=300.0)
    cluster.submit(mkpod("w0", chips=1), 0.0, expected_duration=100.0)
    cluster.assign("w0", "nersc0", 0.0)
    # runway 240 covers the 100s of declared work...
    assert jcs.reprovision(cluster, 0.0, horizon=600.0) == []
    # ...but not 100s + a 600-request backlog at 2 req/s (300s more)
    pilots = jcs.reprovision(cluster, 0.0, horizon=600.0, walltime=3600.0,
                             queue_backlog=600, service_rate=2.0)
    assert len(pilots) == 1


# ---------------------------------------------------- batch site drain

def test_drain_allocation_is_one_wave():
    """drain_allocation cordons every node up front: a displaced pod can
    never re-bind onto a sibling of the same expiring allocation."""
    cluster = mkcluster({"jlab": 2, "nersc": 1}, chips=4, walltime=0.0)
    cluster.apply_deployment(Deployment("web", 2, template=PodTemplate(
        tolerations=list(TOL), request_chips=1)), 0.0)
    plane = ControlPlane(cluster)
    plane.step(0.0)
    jlab_pods = [r for r in cluster.pods_of("web")
                 if r.pod.node and r.pod.node.startswith("jlab")]
    assert jlab_pods                           # spread put work at jlab
    plane.nodes.drain_allocation(["jlab0", "jlab1"], 1.0)
    assert not cluster.node_status["jlab0"].schedulable
    assert not cluster.node_status["jlab1"].schedulable
    plane.step(1.0)
    live = [r for r in cluster.pods_of("web") if r.bound]
    assert len(live) == 2
    assert all(r.pod.node == "nersc0" for r in live)
    # every reschedule event after the wave names the surviving site only
    resched = [e for e in cluster.events
               if e.reason == "Rescheduled" and e.time >= 1.0]
    assert resched and all("nersc0" in e.message for e in resched)


@pytest.fixture(scope="module")
def serving():
    cfg = get_config("qwen2-7b").reduced()
    return ElasticServing(cfg, tp=1).build(1, seed=0)


def test_site_kill_zero_request_loss(serving, tmp_path):
    """Acceptance: replicas spread across two facilities; the whole jlab
    allocation is batch-drained mid-stream (facility kill); every
    in-flight request completes on the surviving site with slot tables
    restored — zero request loss, cross-site."""
    fe = FrontEnd()
    jcs = CentralService(fe)
    cluster = Cluster()
    wfs = fe.add_multi_wf("fed-", {"jlab": 1, "nersc": 1}, nodetype="tpu",
                          walltime=0.0)
    jcs.launch_multi(wfs, now=0.0, slice_spec=SliceSpec(chips=4),
                     cluster=cluster)
    topo = SiteTopology.parse("jlab:nersc:40")
    plane = ControlPlane(cluster, scheduler=Scheduler(cluster, topology=topo))
    plane.nodes.ckpt_dir = str(tmp_path)
    eng = StreamEngine(serving.cfg, serving, jcs.node_list(),
                       service_rate=6.0, max_batch=4, cluster=cluster,
                       plane=plane)
    eng.deploy(0.0)
    cluster.scale("ersap", 2, 0.0, source="test")
    eng.reconcile(0.0)
    assert sorted(cluster.nodes[p.node].site
                  for p in eng.pods.values()) == ["jlab", "nersc"]

    dt = 10.0
    for t in range(12):
        now = t * dt
        if t == 5:
            plane.drain_site("jlab", now)
        for name, node in cluster.nodes.items():
            if node.site != "jlab" or t < 5:
                cluster.heartbeat(name, now)
        eng.reconcile(now)
        eng.tick(now, dt, lam=1.0 if t < 6 else 0.0)

    assert eng.source.rid > 0
    assert len(eng.completed) == eng.source.rid     # zero loss
    assert len(eng.queue) == 0
    assert len(eng.pods) == 2
    assert all(cluster.nodes[p.node].site == "nersc"
               for p in eng.pods.values())
    moved = [r for r in cluster.pods_of("ersap") if r.restored_from]
    assert moved                                    # cross-site reschedule
    assert "SiteDrain" in cluster.event_reasons("jlab")
