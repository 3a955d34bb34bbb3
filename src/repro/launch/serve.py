"""End-to-end JIRIAF serving driver — the paper's proof-of-concept (§5)
re-done as a TPU streaming-inference deployment with the §6 digital twin
in the control loop.

Flow (declarative control plane): JFE add_wf -> JCS pilot launch
(staggered JRM/VK bring-up, SSH port map) -> nodes registered in the
Cluster store -> JFM feeds heartbeats as NodeStatus -> StreamEngine
declares an "ersap" Deployment -> DeploymentController + Scheduler
converge pods -> real batched prefill+decode -> Prometheus scrapes ->
DBN twin (or reactive HPA) writes desired replicas on the Deployment as
the arrival rate follows the §6.2 ground-truth pressure trajectory. A
``--walltime`` lease makes the NodeLifecycleController drain nodes
mid-run: checkpoint, evict, reschedule — visible in the event trail.

Multi-site federation: ``--sites "jlab:2,nersc:2"`` brings up one pilot
per facility (JFE multi-site workflow -> JCS launch_multi), the scheduler
spreads replicas across sites latency-aware (``--site-latency``), and
``--kill-site SITE --kill-tick T`` batch-drains a whole facility mid-run
— its replicas checkpoint and reschedule cross-site with zero request
loss. ``--reprovision`` lets the JCS top up any site whose walltime
runway drops below projected demand — now also sized from the live
serving queue backlog and capacity-starved pending pods (pair with
``--walltime`` to watch the fleet survive perpetual lease churn).

QoS mixed-workload mode: ``--batch-load N`` runs N preemptible batch
pods (priority class ``batch``, one chip each, with a checkpointable
progress counter) next to the serving Deployment; during pressure
spikes the twin escalates serving to ``latency-critical`` (written via
``cluster.set_priority``) so serving scale-ups preempt batch work —
victims checkpoint, requeue, and resume when the spike passes.
``--priority-class`` sets serving's initial tier; ``--quota`` applies
fair-share caps (e.g. ``"ersap:chips=8,batch:chips=6"``).

Paged-slab extras: ``--prefix-cache`` turns on reference-counted
prefix-sharing admission (matching prompts splice onto in-flight pages,
copy-on-write on divergence); ``--spec-decode K`` drafts K tokens per
row and verifies them in one (K+1)-wide dispatch. Both require
``--paged``; end-of-run stats report hit and accept rates.

On the CPU (tests), ``--reduced`` serves the family-preserving tiny
config and ``--devices N`` forges N host devices; on a TPU the devices
are the chips there are, and asking for more is an error at startup.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --reduced \
      --devices 8 --tp 2 --nodes 4 --ticks 80 [--controller hpa] \
      [--walltime 300] \
      [--sites "jlab:2,nersc:2" --site-latency "jlab:nersc:40" \
       --kill-site jlab --kill-tick 40] \
      [--batch-load 6 --quota "ersap:chips=6,batch:chips=6"]
"""
import argparse
import json
import os
import sys


def _pre_jax():
    # host-platform device count must be fixed before jax import; a TPU
    # platform ignores it and reports its own chips
    if "--devices" in sys.argv:
        n = sys.argv[sys.argv.index("--devices") + 1]
        os.environ.setdefault("XLA_FLAGS",
                              f"--xla_force_host_platform_device_count={n}")


_pre_jax()

import jax                                        # noqa: E402
import numpy as np                                # noqa: E402

from repro.configs.base import get_config         # noqa: E402
from repro.core import qos                        # noqa: E402
from repro.core.cluster import Cluster            # noqa: E402
from repro.core.controllers import ControlPlane   # noqa: E402
from repro.core.elastic import ElasticServing     # noqa: E402
from repro.core.hpa import HPA, HPAConfig         # noqa: E402
from repro.core.jcs import CentralService         # noqa: E402
from repro.core.jfe import FrontEnd               # noqa: E402
from repro.core.jfm import FacilityManager        # noqa: E402
from repro.core.jrm import SliceSpec              # noqa: E402
from repro.core.scheduler import Scheduler, SiteTopology  # noqa: E402
from repro.core.digital_twin.queue_model import ground_truth, lam_of_state  # noqa: E402
from repro.data.pipeline import RequestSource     # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.streaming.engine import StreamEngine   # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the family-preserving tiny config (CPU"
                         " tests) instead of the published widths")
    ap.add_argument("--devices", type=int, default=0,
                    help="devices of the slice (0 = every device of the"
                         " platform)")
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--ticks", type=int, default=80)
    ap.add_argument("--dt", type=float, default=10.0)
    ap.add_argument("--controller", choices=["twin", "hpa"], default="twin")
    ap.add_argument("--lam-scale", type=float, default=0.02,
                    help="arrival rate = lam_of_state(s) * scale req/s")
    ap.add_argument("--walltime", type=float, default=0.0,
                    help="per-node lease (s); >0 exercises the drain ->"
                         " checkpoint -> reschedule loop mid-run")
    ap.add_argument("--sites", default="",
                    help='multi-site pilot spec "site:nnodes,..." (e.g.'
                         ' "jlab:2,nersc:2"); overrides --nodes')
    ap.add_argument("--site-latency", default="",
                    help='inter-site latency matrix "a:b:ms,..." for'
                         " latency-weighted cross-site spreading")
    ap.add_argument("--kill-site", default="",
                    help="batch-drain this whole site at --kill-tick"
                         " (checkpoint/evict wave, cross-site reschedule)")
    ap.add_argument("--kill-tick", type=int, default=-1)
    ap.add_argument("--reprovision", action="store_true",
                    help="JCS proactively launches a fresh pilot when a"
                         " site's walltime runway drops below projected"
                         " demand — sized from live queue backlog and"
                         " capacity-starved pods too (pair with"
                         " --walltime)")
    ap.add_argument("--priority-class", default="standard",
                    choices=["batch", "standard", "latency-critical",
                             "system"],
                    help="serving Deployment's initial QoS tier (the twin"
                         " escalates to latency-critical under pressure)")
    ap.add_argument("--quota", default="",
                    help='fair-share quotas "owner[@site]:chips=N'
                         '[:hbm_gb=G][:kv_pages=P],..." enforced as a'
                         " scheduler filter stage")
    ap.add_argument("--batch-load", type=int, default=0,
                    help="mixed-workload mode: run this many preemptible"
                         " batch pods (priority class batch, 1 chip each,"
                         " checkpointable progress) next to serving")
    ap.add_argument("--no-runtime", action="store_true",
                    help="disable the slot-slab serving runtime (fall back"
                         " to the chunked prefill+decode path)")
    ap.add_argument("--vary-shapes", action="store_true",
                    help="randomize per-request prompt_len/max_new (the"
                         " workload bucketed compilation is built for)")
    ap.add_argument("--kernel-mode", choices=["auto", "pallas", "jnp"],
                    default="auto",
                    help="decode-attention dispatch: auto picks Pallas on"
                         " TPU and the jnp block-skip path elsewhere;"
                         " pallas forces the kernels (interpret off-TPU)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV slab: per-request page allocation, "
                         "decode reads only the live kv bucket — wins when"
                         " capacity is provisioned well beyond typical"
                         " request depth (see bench_paged_decode); the"
                         " dense slab with adaptive block-skip is default")
    ap.add_argument("--page-size", type=int, default=16,
                    help="KV entries per physical page of the paged slab")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="physical KV pages per replica (0 = enough for"
                         " max_batch full-capacity requests)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="prefix-sharing admission for the paged slab:"
                         " prompts whose page-aligned prefix matches an"
                         " in-flight request splice onto the existing pages"
                         " (refcounted, copy-on-write) instead of re-running"
                         " prefill — requires --paged")
    ap.add_argument("--spec-decode", type=int, default=0, metavar="K",
                    help="multi-token speculative decode: draft K tokens per"
                         " row and verify them in one (K+1)-wide paged"
                         " dispatch (greedy accept-prefix, token-identical"
                         " to one-at-a-time) — requires --paged")
    ap.add_argument("--prefix-share", type=float, default=0.0,
                    help="traffic shaping: fraction of requests that join a"
                         " shared-prefix template group (makes"
                         " --prefix-cache hits visible from the driver)")
    ap.add_argument("--chaos", default="",
                    help='comma-separated fault schedule "kind:target@at'
                         '[+duration][x<mag>]" (kinds: crash, flap,'
                         " partition, straggler, ckpt_corrupt,"
                         " walltime_cut, surge — surge multiplies the"
                         ' arrival rate by <mag>; target "*" picks a'
                         ' seeded victim), e.g.'
                         ' "partition:n0@120+45,surge:ersap@300+100x6".'
                         " Replaces the heartbeat/JFM block with the"
                         " FaultInjector seam, enables background"
                         " checkpoints, and audits bookkeeping invariants"
                         " every tick")
    ap.add_argument("--event-budget", type=int, default=0,
                    help="cap dirty objects reconciled per controller per"
                         " tick (0 = unbounded); excess carries to the"
                         " next tick — bounds per-tick reconcile latency"
                         " at large fleet sizes")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help='seed for "*" victim selection (same schedule +'
                         " seed => identical fault storm)")
    ap.add_argument("--deadline", type=float, default=0.0, metavar="TTL",
                    help="per-request time-to-live (s): requests carry"
                         " deadline = arrival + TTL and are shed before"
                         " prefill once expired (0 disables)")
    ap.add_argument("--brownout", action="store_true",
                    help="overload protection: bounded arrival queue with"
                         " backpressure, watermark+hysteresis brownout"
                         " (cap max_new, disable spec decode, shed low"
                         " tiers first), and per-replica circuit breakers")
    ap.add_argument("--retry-budget", type=float, default=0.0,
                    metavar="RATE",
                    help="per-tenant retry token-bucket refill rate (/s):"
                         " backpressured retries beyond the budget are"
                         " shed instead of re-queued (0 disables)")
    ap.add_argument("--queue-cap", type=int, default=0,
                    help="arrival FIFO bound (0 = unbounded; --brownout"
                         " defaults it to 64 x service capacity)")
    ap.add_argument("--trace", action="store_true",
                    help="request-lifecycle tracing: every hop of every"
                         " request (enqueue/admit/prefill/decode/drain/"
                         "restore/retire + control-plane spans) lands in"
                         " a bounded span ring with an SLO flight"
                         " recorder on top")
    ap.add_argument("--trace-out", default="", metavar="FILE",
                    help="write the flight-recorder dump (span ring +"
                         " events + incidents) as JSON at end of run;"
                         " implies --trace. Render with tools/tracedump.py")
    ap.add_argument("--metrics-out", default="", metavar="FILE",
                    help="dump the full metric pipeline as Prometheus"
                         ' text exposition at end of run ("-" = stdout)')
    ap.add_argument("--incident-dir", default="", metavar="DIR",
                    help="flight recorder auto-dumps incident bundles"
                         " (SLO breach / invariant violation) here")
    ap.add_argument("--slo-p99", type=float, default=0.0, metavar="S",
                    help="latency-critical p99 completion-latency SLO (s):"
                         " a burn-rate breach trips a flight-recorder"
                         " incident (0 disables)")
    ap.add_argument("--site-bandwidth", default="",
                    help='inter-site bandwidth matrix "a:b:gbps,..." for'
                         " the checkpoint transfer-cost model paid by"
                         " drain_site failover and preemption ranking"
                         " (pairs with --site-latency)")
    args = ap.parse_args(argv)
    if (args.prefix_cache or args.spec_decode) and not args.paged:
        ap.error("--prefix-cache/--spec-decode require --paged (they are"
                 " page-table features of the paged KV slab)")
    if args.spec_decode < 0:
        ap.error("--spec-decode must be >= 0")
    if args.kill_site:
        if not (0 <= args.kill_tick < args.ticks):
            ap.error("--kill-site needs --kill-tick in [0, --ticks)")
        known = {part.split(":")[0].strip()
                 for part in args.sites.split(",") if part.strip()}
        if args.kill_site not in known:
            ap.error(f"--kill-site {args.kill_site!r} not in --sites spec")

    n_dev = len(jax.devices())
    args.devices = args.devices or n_dev
    if args.devices > n_dev:
        ap.error(f"--devices {args.devices}: the {jax.default_backend()} "
                 f"platform has {n_dev}")
    if args.tp > args.devices:
        ap.error(f"--tp {args.tp} exceeds --devices {args.devices}")
    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    # kernel dispatch is resolved once, before any jit closure is traced
    from repro.kernels import ops as OPS
    OPS.set_kernel_mode(args.kernel_mode)
    print(f"[kernels] mode={args.kernel_mode} "
          f"(resolved {OPS.resolved_mode()}; backend={jax.default_backend()}"
          f"{'' if OPS.on_tpu() else ', pallas would run interpreted'}); "
          f"paged={'on' if args.paged else 'off'} "
          f"prefix_cache={'on' if args.prefix_cache else 'off'} "
          f"spec_decode={args.spec_decode or 'off'}")

    # ---- JIRIAF control plane bring-up (paper §3 component flow) ----
    fe = FrontEnd()
    jcs = CentralService(fe)
    cluster = Cluster()
    if args.sites:
        site_nodes = {s: int(n) for s, n in
                      (part.split(":") for part in args.sites.split(","))}
        n_nodes = sum(site_nodes.values())
        wfs = fe.add_multi_wf("vk-tpu-", site_nodes, nodetype="tpu",
                              walltime=args.walltime)
        pilots = jcs.launch_multi(
            wfs, now=0.0, cluster=cluster,
            slice_spec=SliceSpec(chips=max(args.devices // n_nodes, 1)))
    else:
        wf = fe.add_wf("vk-tpu-", args.nodes, nodetype="tpu", site="tpu-pod",
                       walltime=args.walltime)
        pilots = [jcs.launch_pilot(wf, now=0.0, cluster=cluster,
                                   slice_spec=SliceSpec(
                                       chips=max(args.devices // args.nodes,
                                                 1)))]
    nodes = jcs.node_list()
    for n in nodes:
        cluster.heartbeat(n.name, 0.0)
    fm = FacilityManager()
    fm.feed(cluster, 0.0)
    topo = SiteTopology.parse(args.site_latency, "", args.site_bandwidth) \
        if (args.site_latency or args.site_bandwidth) else None
    plane = ControlPlane(cluster, scheduler=Scheduler(cluster,
                                                      topology=topo),
                         event_budget=args.event_budget)
    for pilot in pilots:
        print(f"[jcs] pilot {pilot.wf_id}: {len(pilot.nodes)} JRM nodes, "
              f"{len(pilot.tunnels)} SSH tunnels")
    for site, view in cluster.site_views(0.0).items():
        print(f"[site] {site}: {view.ready_nodes}/{view.nodes} ready, "
              f"{view.free_chips} free chips, "
              f"runway={view.remaining_walltime:.0f}s")
    print(f"[jfm] pool: {fm.total_free_chips()} free chips on "
          f"{len(fm.available())} ready nodes")

    # ---- model + elastic serving ----
    serving = ElasticServing(cfg, tp=args.tp).build(1, seed=0)
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(serving.params))
    print(f"[model] {cfg.name}: {cfg.n_layers} layers, d_model "
          f"{cfg.d_model}, {cfg.n_heads}/{cfg.n_kv_heads} heads, vocab "
          f"{cfg.vocab}; {n_bytes / 1e9:.2f} GB of {cfg.dtype} weights "
          f"over {args.tp} device(s) per replica")
    # service rate per replica = mu(16 threads) scaled like the arrivals, so
    # one replica is near-critical at high pressure (M/M/1 analog) and the
    # twin's 2x escalation actually drains the queue.
    mu_scaled = 167.0 * args.lam_scale
    src_kw = {}
    if args.prefix_share > 0:
        # share at least one full page so hits splice real KV, not just
        # the intern-table bookkeeping
        src_kw = dict(prefix_share=args.prefix_share,
                      prefix_len=args.page_size, prefix_groups=4)
    if args.deadline > 0:
        src_kw["ttl"] = args.deadline
    source = RequestSource(**src_kw)
    if args.vary_shapes:
        source = RequestSource(prompt_range=(8, 48), max_new_range=(2, 16),
                               **src_kw)
    from repro.streaming.runtime import RuntimeConfig
    engine = StreamEngine(cfg, serving, nodes,
                          service_rate=mu_scaled,
                          use_twin=(args.controller == "twin"),
                          use_runtime=not args.no_runtime,
                          priority_class=args.priority_class,
                          runtime_cfg=RuntimeConfig(
                              paged=args.paged,
                              page_size=args.page_size,
                              pool_pages=args.pool_pages,
                              prefix_cache=args.prefix_cache,
                              spec_decode=args.spec_decode,
                              # spec acceptance is resolved per round on the
                              # host; the fused admission tail would race it
                              admit_tail=0 if args.spec_decode else 4),
                          source=source,
                          hpa=HPA(HPAConfig(target=8.0, max_replicas=
                                            serving.max_replicas(),
                                            scale_down_stabilization=120.0,
                                            occupancy_target=0.85)),
                          cluster=cluster, plane=plane)
    # ---- overload protection layer (opt-in) ----
    if args.brownout:
        engine.brownout = qos.BrownoutController(delay_target_s=3 * args.dt)
        engine.breaker = qos.ReplicaBreaker(probe_after_s=3 * args.dt)
        engine.queue_cap = args.queue_cap or int(64 * mu_scaled * args.dt)
    elif args.queue_cap:
        engine.queue_cap = args.queue_cap
    if args.retry_budget > 0:
        engine.retry_budget = qos.RetryBudget(rate=args.retry_budget)
        if not engine.queue_cap:
            engine.queue_cap = int(64 * mu_scaled * args.dt)
    if engine.queue_cap or args.brownout or args.deadline:
        print(f"[overload] queue_cap={engine.queue_cap or 'off'} "
              f"brownout={'on' if args.brownout else 'off'} "
              f"retry_budget={args.retry_budget or 'off'}/s "
              f"deadline={args.deadline or 'off'}s")
    # the chosen class is the twin policy's *resting* tier (otherwise the
    # first calm control step would demote a user-chosen tier back to
    # "standard"); a class at/above the escalation tier also becomes the
    # escalation target so pressure never demotes it
    engine.policy.prio_low = args.priority_class
    if cluster.resolve_priority(args.priority_class).value >= \
            cluster.resolve_priority(engine.policy.prio_high).value:
        engine.policy.prio_high = args.priority_class
    if args.quota:
        for q in qos.parse_quotas(args.quota):
            cluster.apply_quota(q, 0.0)
            print(f"[qos] quota {q.owner}"
                  f"{'@' + q.site if q.site else ''}: chips={q.chips} "
                  f"hbm={q.hbm_bytes} kv_pages={q.kv_pages}")
    # ---- unified observability plane (opt-in tracing, always-on profiler) --
    from repro.core.observability import FlightRecorder, SLOConfig, \
        TickProfiler
    from repro.core.tracing import Tracer
    profiler = TickProfiler()
    tracer = recorder = None
    if args.trace or args.trace_out or args.incident_dir or args.slo_p99 > 0:
        tracer = Tracer()
        recorder = FlightRecorder(
            tracer, slo=SLOConfig(lc_p99_s=args.slo_p99),
            dump_dir=args.incident_dir or None)
        print(f"[trace] lifecycle tracing on (ring={tracer.cap} spans); "
              f"slo_p99={args.slo_p99 or 'off'} "
              f"incident_dir={args.incident_dir or 'off'}")
    # wired before deploy so initial schedule/bind spans are captured
    engine.enable_observability(tracer=tracer, recorder=recorder,
                                profiler=profiler)
    engine.deploy(0.0)
    print(f"[scheduler] {len(engine.pods)} serving pods bound; "
          f"controller={args.controller} "
          f"priority={args.priority_class}")

    # ---- mixed-workload batch tenant (QoS preemption target) ----
    batch = None
    if args.batch_load:
        batch = qos.BatchTenant(cluster, args.batch_load, now=0.0)
        engine.reconcile(0.0)
        print(f"[qos] batch tenant: {batch.bound}/{args.batch_load}"
              f" preemptible pods bound")

    # ---- chaos fault injection (seeded, declarative schedule) ----
    injector = auditor = None
    if args.chaos:
        import tempfile
        from repro.core.chaos import FaultInjector, InvariantAuditor
        if not plane.nodes.ckpt_dir:
            plane.nodes.ckpt_dir = tempfile.mkdtemp(prefix="serve-chaos-")
        if plane.nodes.bg_checkpoint_every <= 0:
            # periodic snapshots bound how far a crash can roll back
            plane.nodes.bg_checkpoint_every = args.dt
        injector = FaultInjector(
            schedule=[s.strip() for s in args.chaos.split(",") if s.strip()],
            seed=args.chaos_seed, ckpt_dir=plane.nodes.ckpt_dir)
        auditor = InvariantAuditor(cluster, engine, recorder=recorder)
        print(f"[chaos] {len(injector.schedule)} faults scheduled "
              f"(seed={args.chaos_seed}); bg checkpoints every "
              f"{plane.nodes.bg_checkpoint_every:.0f}s -> "
              f"{plane.nodes.ckpt_dir}")

    # ---- drive with the §6.2 pressure trajectory ----
    gt = ground_truth(args.ticks)
    killed_sites = set()
    for t, s in enumerate(gt):
        now = t * args.dt
        lam = lam_of_state(s) * args.lam_scale
        if args.kill_site and t == args.kill_tick:
            print(f"[federation] t={t}: batch-draining site "
                  f"{args.kill_site} ({len(cluster.site_nodes(args.kill_site))}"
                  f" nodes) — cross-site failover")
            plane.drain_site(args.kill_site, now)
            killed_sites.add(args.kill_site)
        if args.reprovision:
            for pilot in jcs.reprovision(
                    cluster, now, horizon=args.walltime or 600.0,
                    walltime=args.walltime or 600.0,
                    queue_backlog=len(engine.queue),
                    # per-replica rate: backlog/rate is pod-seconds of
                    # work, the same unit projected_demand sums
                    service_rate=mu_scaled):
                wf = fe.table[pilot.wf_id]
                print(f"[jcs] t={t}: demand high at {wf.site} — reprovision"
                      f" pilot {pilot.wf_id} ({len(pilot.nodes)} nodes)")
        if injector is not None:
            # one chaos tick: fire due faults, drive heartbeats for every
            # node that can still send them, feed the JFM, overlay flaps
            injector.apply(cluster, now, fm=fm)
            # flash-crowd seam: active surge windows multiply the ersap
            # stream's arrival rate through the real RequestSource
            engine.source.surge = injector.surge_factor("ersap")
        else:
            for name, node in cluster.nodes.items():
                if node.site not in killed_sites:
                    cluster.heartbeat(name, now)
            fm.feed(cluster, now)
        engine.reconcile(now)          # controllers converge every tick
        if batch is not None:
            batch.advance()            # bound pods progress; resumed pods
            #                            recover from their checkpoint
        qlen = engine.tick(now, args.dt, lam)
        if auditor is not None:
            with profiler.phase("tick.audit"):
                auditor.audit(now)     # books must balance on every tick
        if recorder is not None:
            recorder.check(now)        # burn-rate SLO evaluation
        if t % 2 == 1:
            engine.control_step(now)
        if t % 10 == 0:
            print(f"t={t:3d} state={s:.1f} lam={lam:6.1f} queue={qlen:4d} "
                  f"replicas={engine.serving.replicas} "
                  f"control={engine.control} served={engine.total_served}")

    lat = [engine.registries[r].histogram("ersap_latency_s").mean
           for r in engine.registries if
           engine.registries[r].metrics.get("ersap_latency_s")]
    print(f"[done] served={engine.total_served} requests, "
          f"{engine.total_tokens} tokens; "
          f"scale events={engine.serving.scale_events}; "
          f"mean latency={np.mean(lat) if lat else 0:.1f}s; "
          f"final queue={len(engine.queue)}")
    if engine.runtimes:
        rt = next(iter(engine.runtimes.values()))
        tc = rt.kernels.trace_counts
        blocks = sum(r.steps_dispatched for r in engine.runtimes.values())
        print(f"[runtime] slot-slab serving: traces admit={tc['admit']} "
              f"decode={tc['decode']} (bound {rt.kernels.max_traces}); "
              f"fused blocks={blocks}")
        if rt.kernels.rcfg.paged:
            hwm = max(r.pages_hwm for r in engine.runtimes.values())
            rc = rt.kernels.rcfg
            print(f"[runtime] paged KV slab: page_size={rc.page_size} "
                  f"pool={rc.n_pool_pages} pages/replica; "
                  f"high-water={hwm} pages "
                  f"({hwm * rc.page_size} KV entries vs "
                  f"{(rc.max_batch + 1) * rc.capacity} dense)")
            if rc.prefix_cache:
                hits = sum(r.prefix_hits for r in engine.runtimes.values())
                looks = sum(r.prefix_lookups
                            for r in engine.runtimes.values())
                cows = sum(r.cow_events for r in engine.runtimes.values())
                print(f"[runtime] prefix cache: {hits}/{looks} admission "
                      f"hits; {cows} copy-on-write events; "
                      f"traces splice={tc['splice']} window={tc['window']} "
                      f"cow={tc['cow']}")
            if rc.spec_decode:
                drafted = sum(r.spec_drafted
                              for r in engine.runtimes.values())
                accepted = sum(r.spec_accepted
                               for r in engine.runtimes.values())
                rate = accepted / max(drafted, 1)
                print(f"[runtime] speculative decode: k={rc.spec_decode} "
                      f"drafted={drafted} accepted={accepted} "
                      f"(accept rate {rate:.2f})")
    if engine.queue_cap or engine.brownout is not None or \
            engine.retry_budget is not None or args.deadline:
        bl = engine.brownout.level if engine.brownout is not None else 0
        trans = len(engine.brownout.transitions) \
            if engine.brownout is not None else 0
        print(f"[overload] shed={dict(sorted(engine.shed_counts.items()))} "
              f"rejected={engine.rejected_total} "
              f"retried={engine.retried_total} "
              f"brownout_level={bl} transitions={trans} "
              f"transfer_windows={engine.transfer_windows}")
        if engine.breaker is not None and engine.breaker.ejections:
            print(f"[overload] breaker: {engine.breaker.ejections} ejected,"
                  f" {engine.breaker.rejoins} rejoined")
    if len(cluster.site_names()) > 1:
        per_site = {}
        for pod in engine.pods.values():
            node = cluster.nodes.get(pod.node)
            if node is not None:
                per_site[node.site] = per_site.get(node.site, 0) + 1
        print(f"[federation] replicas by site: {dict(sorted(per_site.items()))}")
    trail = {}
    for ev in cluster.events:
        trail[ev.reason] = trail.get(ev.reason, 0) + 1
    print(f"[events] {dict(sorted(trail.items()))}")
    if injector is not None:
        fired = {}
        for _, kind, target in injector.log:
            fired[kind] = fired.get(kind, 0) + 1
        print(f"[chaos] faults fired: {dict(sorted(fired.items()))}; "
              f"audits passed={auditor.checks}; "
              f"fence floors outstanding={len(cluster.fence_epochs)}")
    if batch is not None:
        print(f"[qos] batch: {batch.bound}/{args.batch_load} bound at end, "
              f"{trail.get('Preempted', 0)} preemptions, "
              f"{trail.get('PriorityChanged', 0)} priority writes, "
              f"{len(batch.resumed)} resumed from checkpoint, "
              f"total progress={batch.total_progress}")
        books = cluster.ledger.assert_balanced()
        print(f"[qos] quota books: chips {books['chips_used']} used + "
              f"{books['chips_free']} free == {books['chips_capacity']}")
    prof = profiler.summary()
    if prof:
        top = sorted(prof.items(), key=lambda kv: -kv[1]["total_s"])
        print("[profile] " + " ".join(
            f"{name}={p['total_s']:.3f}s/{p['calls']}" for name, p in top))
    if recorder is not None:
        spans = recorder.tracer.spans
        rids = {s.rid for s in spans if s.rid}
        print(f"[trace] {len(spans)} spans across {len(rids)} requests "
              f"({recorder.tracer.dropped} dropped); "
              f"{len(recorder.incidents)} incidents")
        if args.trace_out:
            with open(args.trace_out, "w") as fh:
                json.dump(recorder.dump(), fh)
            print(f"[trace] flight-recorder dump -> {args.trace_out}")
    if args.metrics_out:
        text = engine.exposition()
        if args.metrics_out == "-":
            print(text, end="")
        else:
            with open(args.metrics_out, "w") as fh:
                fh.write(text)
            print(f"[metrics] prometheus exposition "
                  f"({len(text.splitlines())} lines) -> {args.metrics_out}")
    return engine


if __name__ == "__main__":
    main()
