"""ERSAP-analog streaming inference engine (paper §5 workload + §6 queue).

Pipeline: RequestSource (Poisson sender) -> FIFO queue -> per-replica
**decode runtimes** (slot-slab continuous batching,
``repro.streaming.runtime``) -> sink.

Serving path (PR 2, paged in PR 4): each bound replica owns a
``DecodeRuntime`` — a paged KV slab (``max_batch`` slots over a shared
pool of fixed-size pages, page-aware admission/retirement, decode cost
proportional to live tokens) with bucketed-compilation admission and a
fused ``lax.scan`` decode block. ``ersap_kv_pages`` gauges per-replica
pool occupancy. ``tick()`` meters
requests off the FIFO queue by a fractional service budget (no more
integer-truncation starvation at low rates), submits them to the
replica's runtime, and pumps it to quiescence: finished requests free
their slots mid-stream and pending ones are admitted immediately, so the
number of jit traces stays O(#buckets) and short requests stop riding
along for their chunk-mates' ``max_new``. Families without a slot-slab
decode (recurrent caches) and oversized requests fall back to the legacy
chunked path. The runtime's slot table is part of the replica's
checkpoint state, so in-flight requests survive the §4.5.4 drain ->
checkpoint -> evict -> reschedule loop.

Declarative control plane: the engine declares a ``Deployment`` ("ersap")
in the Cluster store; the DeploymentController converges
``spec.replicas`` -> pods, the Scheduler places them (spread across
nodes, straggler-averse), and the NodeLifecycleController drains
walltime-expiring nodes — checkpointing each replica's runtime state via
``repro.checkpoint`` so the rescheduled replica resumes its counters and
its slot table. The HPA and the digital-twin policy are both
*desired-replica writers*: ``control_step`` computes a target and writes
``Deployment.replicas``; reconciliation does the rest. Metrics (queue
depth, served, latency) flow through the §4.6 monitoring stack, whose
Service endpoints (and control-plane port map) are rebuilt from live pods
every sync — retired replicas leave no stale scrape targets or ports.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import qos
from repro.core.cluster import Cluster, Deployment, PodTemplate
from repro.core.controllers import ControlPlane
from repro.core.hpa import HPA, HPAConfig, PressureSignals
from repro.core.jrm import VirtualNode
from repro.core.metrics import (COUNT_BUCKETS, Endpoint, Prometheus,
                                Registry, Service, ServiceMonitor,
                                split_series)
from repro.core.observability import render_exposition
from repro.core.state_machine import Pod
from repro.core.digital_twin.control import ControlPolicy, replicas_for_control
from repro.core.digital_twin.dbn import DigitalTwin
from repro.data.pipeline import Request, RequestSource
from repro.models import model_api as MA
from repro.streaming.runtime import (DecodeRuntime, RuntimeConfig,
                                     requests_from_state)

DEPLOYMENT = "ersap"


@dataclass
class ReplicaStats:
    served: int = 0
    tokens: int = 0


@dataclass
class StreamEngine:
    cfg: ArchConfig
    serving: object                   # ElasticServing
    nodes: List[VirtualNode]
    max_batch: int = 8
    service_rate: float = 40.0        # requests/s one replica can absorb
    queue: List[Request] = field(default_factory=list)
    source: RequestSource = field(default_factory=RequestSource)
    registries: Dict[str, Registry] = field(default_factory=dict)
    prom: Prometheus = field(default_factory=Prometheus)
    stats: Dict[str, ReplicaStats] = field(default_factory=dict)
    completed: list = field(default_factory=list)
    control: int = 16
    twin: DigitalTwin = field(default_factory=DigitalTwin)
    policy: ControlPolicy = field(default_factory=ControlPolicy)
    hpa: Optional[HPA] = None
    base_replicas: int = 1
    use_twin: bool = True
    priority_class: str = "standard"  # serving Deployment's initial tier
    use_runtime: bool = True          # slot-slab runtime (when family allows)
    runtime_cfg: Optional[RuntimeConfig] = None
    # per-rid greedy token logs on every replica runtime (needs a
    # runtime_cfg with admit_tail=0): the chaos bench's oracle-comparison
    # evidence that recovery is token-identical, never duplicated
    record_tokens: bool = False
    history: list = field(default_factory=list)
    # declarative control plane (built from ``nodes`` unless injected)
    cluster: Optional[Cluster] = None
    plane: Optional[ControlPlane] = None
    total_served: int = 0
    total_tokens: int = 0
    tokens_rate: float = 0.0          # tokens/s over the last tick (HPA signal)
    runtimes: Dict[str, DecodeRuntime] = field(default_factory=dict)
    _cp_ports: Dict[str, int] = field(default_factory=dict)
    _next_cp_port: int = 20000
    _budget_frac: float = 0.0         # fractional service budget carry
    # last known node per replica: when a pod vanishes from the store we
    # still need to know whether its node was reachable (partition vs
    # graceful retire) to pick the right recovery path in _sync
    _pod_nodes: Dict[str, str] = field(default_factory=dict)
    # ------------- overload protection & graceful degradation ----------
    # bounded arrival FIFO (0 = unbounded): overflow is backpressured to
    # the RequestSource (reject-with-retry-after) instead of growing
    queue_cap: int = 0
    brownout: Optional[qos.BrownoutController] = None
    retry_budget: Optional[qos.RetryBudget] = None
    breaker: Optional[qos.ReplicaBreaker] = None
    # per-rid greedy-log ring cap handed to every runtime (0 = unbounded)
    token_log_cap: int = 0
    # cost-modeled failover: while now < degrade_until (set by the
    # drain_site transfer window) the engine serves at least at this
    # brownout level — shed the batch tier, protect latency-critical
    transfer_degrade_level: int = 2
    degrade_until: float = 0.0
    transfer_windows: int = 0
    shed: list = field(default_factory=list)        # (rid, reason, now)
    _level: int = 0               # effective brownout level this tick
    _last_dt: float = 1.0
    # ---------------------- observability plane ------------------------
    # engine-level registry (pod label "_engine" in the exposition):
    # queue/brownout gauges, queue-wait histogram, shed/reject/retry
    # counters — the one place overload accounting lives (the old ad-hoc
    # shed_counts/rejected_total/retried_total are compat views below)
    metrics: Registry = field(default_factory=Registry)
    tracer: object = None             # repro.core.tracing.Tracer
    recorder: object = None           # repro.core.observability.FlightRecorder
    profiler: object = None           # repro.core.observability.TickProfiler
    # rid -> sim-time its drain span landed (restore-latency burn input)
    _drain_t: Dict[int, float] = field(default_factory=dict)

    # ------------------- overload accounting (compat) ------------------
    @property
    def shed_counts(self) -> Dict[str, int]:
        """Per-reason shed counts, read back from the labeled
        ``ersap_shed_total`` counter series (compat shim for the old
        ad-hoc dict)."""
        out: Dict[str, int] = {}
        for key, m in self.metrics.metrics.items():
            base, lbl = split_series(key)
            if base == "ersap_shed_total" and lbl:
                reason = lbl[1:-1].split("=", 1)[1].strip('"')
                out[reason] = int(m.value)
        return out

    @property
    def rejected_total(self) -> int:
        return int(self.metrics.counter("ersap_rejected_total").value)

    @property
    def retried_total(self) -> int:
        return int(self.metrics.counter("ersap_retried_total").value)

    # ------------------------------------------------------------ setup
    @property
    def pods(self) -> Dict[str, Pod]:
        """Live bound pods of the ersap Deployment (status view)."""
        if self.cluster is None:
            return {}
        return {r.name: r.pod
                for r in self.cluster.pods_of(DEPLOYMENT) if r.bound}

    def _ensure_plane(self, now: float):
        if self.cluster is None:
            self.cluster = Cluster()
        for n in self.nodes:
            if n.name not in self.cluster.nodes:
                self.cluster.register_node(n, now)
        if self.plane is None:
            self.plane = ControlPlane(self.cluster)
        self._wire_plane_obs()
        if self.plane.on_transfer is None:
            # drain_site reports its checkpoint-transfer window here so
            # the engine serves degraded while state crosses facilities
            self.plane.on_transfer = self._on_transfer
        if self.runtime_cfg is None:
            self.runtime_cfg = RuntimeConfig(max_batch=self.max_batch)

    def _replica_state(self, name: str) -> Optional[dict]:
        st = self.stats.get(name)
        if st is None:
            return None
        state = {"served": st.served, "tokens": st.tokens}
        rt = self.runtimes.get(name)
        if rt is not None:
            # credit partial generation now; the successor replica credits
            # only the checkpointed remainder at finish, so per-request
            # token totals stay exact across a reschedule
            state["tokens"] = st.tokens + rt.partial_tokens()
            state.update(rt.state())
        return state

    def deploy(self, now: float = 0.0):
        """Declare (or re-declare) the serving Deployment at the current
        replica count and reconcile until pods and monitoring converge."""
        self._ensure_plane(now)
        if DEPLOYMENT not in self.cluster.deployments:
            self.cluster.apply_deployment(Deployment(
                DEPLOYMENT, self.serving.replicas,
                template=PodTemplate(
                    labels={"app": "ersap"},
                    tolerations=[{"key": "virtual-kubelet.io/provider",
                                  "value": "mock"}],
                    request_chips=self.serving.tp,
                    priority_class=self.priority_class,
                    # declared KV footprint per replica: what the
                    # kv_pages quota dimension charges at schedule time
                    request_kv_pages=(self.runtime_cfg.n_pool_pages
                                      if self.runtime_cfg.paged else 0),
                    checkpoint_state=self._replica_state)), now)
        else:
            self.cluster.scale(DEPLOYMENT, self.serving.replicas, now,
                               source="engine")
        self.reconcile(now)

    def reconcile(self, now: float):
        """One control-plane step + engine-side sync (registries, stats,
        runtimes, Service endpoints follow the pod set — nothing leaks on
        retire)."""
        self._ensure_plane(now)
        self.plane.step(now)
        self._sync(now)

    # ----------------------------------------------------------- runtimes
    def _make_runtime(self, name: str) -> Optional[DecodeRuntime]:
        if not (self.use_runtime and MA.supports_slots(self.cfg)):
            return None
        kernels = self.serving.runtime_kernels(self.runtime_cfg)
        return DecodeRuntime(kernels, self.serving.params,
                             gen=self.serving.build_gen,
                             record_tokens=self.record_tokens,
                             token_log_cap=self.token_log_cap,
                             name=name, tracer=self.tracer,
                             metrics=self.registries.get(name))

    def _credit_partial(self, name: str, rt: DecodeRuntime):
        """Credit partial generation of in-flight slots before their
        requests are requeued with max_new = remaining, so partial +
        finish-time credit sums to exactly the original max_new."""
        partial = rt.partial_tokens()
        if not partial:
            return
        st = self.stats.get(name)
        if st is not None:
            st.tokens += partial
        self.total_tokens += partial

    def _known_rids(self) -> set:
        """Request ids already accounted for somewhere in the engine."""
        rids = {r.rid for r in self.queue}
        for rt in self.runtimes.values():
            rids.update(r.rid for r in rt.pending)
            rids.update(s.req.rid for s in rt.slots if s.busy)
        rids.update(rid for rid, _ in self.completed)
        return rids

    def _refresh_runtime(self, name: str) -> Optional[DecodeRuntime]:
        """Replica's runtime, rebuilt (in-flight preserved) whenever the
        serving mesh was re-built underneath it."""
        rt = self.runtimes.get(name)
        if rt is not None and rt.gen != self.serving.build_gen:
            self._credit_partial(name, rt)
            carried = rt.drain()
            rt = self._make_runtime(name)
            if rt is not None:
                rt.submit(carried, force=True)
                self.runtimes[name] = rt
            else:
                self.queue = carried + self.queue
                self.runtimes.pop(name, None)
        return rt

    def _node_reachable(self, name: str) -> bool:
        """Whether the replica's (last known) node is control-plane
        reachable. Unknown nodes count as reachable."""
        node = self._pod_nodes.get(name)
        st = self.cluster.node_status.get(node) if node else None
        return st is None or st.reachable

    def _sync(self, now: float):
        live = {r.name: r for r in self.cluster.pods_of(DEPLOYMENT)
                if r.bound}
        for name, rec in live.items():
            if rec.pod.node:
                self._pod_nodes[name] = rec.pod.node
        for name in list(self.registries):
            if name not in live:
                rt = self.runtimes.pop(name, None)
                if rt is not None:
                    if self._node_reachable(name):
                        # graceful retire: credit partial output, hand
                        # back in-flight with max_new = remaining
                        self._credit_partial(name, rt)
                    # else: partition — the replica's streamed output is
                    # unobservable, so nothing is credited; the frontend
                    # re-issues its in-flight requests (zero loss even
                    # for rids admitted after the last checkpoint) and
                    # they replay from the prompt. Checkpoint-restored
                    # copies of the same rids dedupe against these queue
                    # entries below, and the orphaned replica itself is
                    # epoch-fenced on rejoin, so nothing double-emits.
                    drained = rt.drain()
                    for r in drained:
                        self._drain_t[r.rid] = now
                        if self.tracer is not None:
                            self.tracer.span("drain", now, rid=r.rid,
                                             replica=name)
                    self.queue = drained + self.queue
                self.registries.pop(name, None)
                self.stats.pop(name, None)
                self._pod_nodes.pop(name, None)
        # prune the §4.6.3 control-plane port map with the registries —
        # ports stay stable for live pods but no longer grow monotonically
        # across evict/reschedule cycles
        for name in list(self._cp_ports):
            if name not in live:
                self._cp_ports.pop(name)
        for name, rec in sorted(live.items()):
            if name in self.registries:
                continue
            self.registries[name] = Registry(port=2221)
            st = ReplicaStats()
            rt = self._make_runtime(name)
            if rt is not None:
                self.runtimes[name] = rt
            if rec.restored_state:
                st.served = int(rec.restored_state.get("served", 0))
                st.tokens = int(rec.restored_state.get("tokens", 0))
                # slot table survives drain -> checkpoint -> reschedule:
                # in-flight requests resume on the replacement replica.
                # Dedupe against requests already handed back through the
                # retire path above (the evicted replica's runtime drains
                # into the queue AND its checkpoint names the same rids —
                # each request must be served exactly once).
                known = self._known_rids()
                from_ckpt = requests_from_state(rec.restored_state)
                for r in from_ckpt:
                    t0 = self._drain_t.pop(r.rid, now)
                    if self.tracer is not None:
                        # restore spans bump the rid's incarnation in the
                        # tracer, so post-restore hops are distinguishable
                        self.tracer.span("restore", now, rid=r.rid,
                                         replica=name)
                    if self.recorder is not None:
                        self.recorder.note_restore(now, now - t0)
                restored = [r for r in from_ckpt if r.rid not in known]
                if rt is not None:
                    # content store rides the checkpoint: restored rids
                    # replay their exact prompt tokens
                    rt.ingest_content(rec.restored_state)
                    rt.submit(restored, force=True)
                else:
                    self.queue = restored + self.queue
            self.stats[name] = st
        # Service endpoints rebuilt from live pods only (§4.6.3 port remap
        # stays unique per pod even though all VK pods share one pod IP)
        svc = Service("ersap-metrics", selector={"app": "ersap"},
                      labels={"monitored": "true"})
        for name, rec in sorted(live.items()):
            node = self.cluster.nodes.get(rec.pod.node)
            if node is None:
                continue
            if name not in self._cp_ports:
                self._cp_ports[name] = self._next_cp_port
                self._next_cp_port += 1
            svc.add_endpoint(Endpoint(
                pod=name, pod_ip=node.pod_ip, port=2221,
                cp_port=self._cp_ports[name], registry=self.registries[name]))
        self.prom.services = [svc]
        if not self.prom.monitors:
            self.prom.monitors = [ServiceMonitor(
                "ersap-mon", service_selector={"monitored": "true"})]

    # --------------------------------------------- overload protection
    def _on_transfer(self, now: float, window: float):
        """drain_site failover hook: the checkpoint-transfer window just
        started — serve degraded (shed batch, protect latency-critical)
        until the state has physically arrived at the destination site."""
        self.degrade_until = max(self.degrade_until, now + window)
        self.transfer_windows += 1
        # span emission lives in ControlPlane.drain_site (site context);
        # here we only feed the flight recorder's burn-rate windows
        if self.recorder is not None:
            self.recorder.event(now, "transfer", f"window={window:.2f}s")
            self.recorder.note_restore(now, window)

    def _shed(self, req: Request, reason: str, now: float):
        self.shed.append((req.rid, reason, now))
        self.metrics.counter("ersap_shed_total",
                             labels={"reason": reason}).inc()
        if self.tracer is not None:
            self.tracer.span("shed", now, rid=req.rid, reason=reason)
        if self.recorder is not None:
            self.recorder.note_shed(now)

    def _backpressure(self, overflow: List[Request], now: float):
        """Bounded-queue rejection: estimate retry-after from backlog vs
        capacity, then per request either shed (deadline unreachable, or
        the tenant's retry budget is dry — no retry storms) or defer back
        through the RequestSource for a client-side retry."""
        self.metrics.counter("ersap_rejected_total").inc(len(overflow))
        cap = self.service_rate * max(len(self.registries), 1)
        retry_after = max(self._last_dt,
                          len(self.queue) / max(cap, 1e-9))
        for r in overflow:
            if r.deadline > 0 and now + retry_after > r.deadline:
                self._shed(r, "deadline", now)
            elif self.retry_budget is not None and not self.retry_budget \
                    .allow(qos.tier_label(r.priority), now):
                self._shed(r, "retry-budget", now)
            else:
                self.source.defer([r], now + retry_after)
                self.metrics.counter("ersap_retried_total").inc()

    def _police_queue(self, now: float):
        """Deadline-aware admission + brownout shedding, applied to the
        whole FIFO *before* any request reaches prefill: expired requests
        and tiers below the current shed floor never burn compute."""
        floor = qos.shed_floor_for_level(self._level)
        shed0 = len(self.shed)
        keep: List[Request] = []
        for r in self.queue:
            if r.deadline > 0 and now > r.deadline:
                self._shed(r, "deadline", now)
            elif floor and r.priority < floor:
                self._shed(r, "brownout", now)
            else:
                keep.append(r)
        self.queue = keep
        if self.tracer is not None and len(self.shed) > shed0:
            self.tracer.span("police", now, kept=len(keep),
                             shed=len(self.shed) - shed0)

    def _degrade_cap(self) -> int:
        return (self.brownout.degrade_max_new if self.brownout is not None
                else qos.BrownoutController.degrade_max_new)

    # ------------------------------------------------------------- tick
    def tick(self, now: float, dt: float, lam: float):
        """One engine step of simulated time dt with arrival rate lam.
        Capacity follows the *actual* replica set in the cluster store."""
        self._last_dt = dt
        arrivals = self.source.arrivals(now, dt, lam)
        if self.queue_cap > 0 and \
                len(self.queue) + len(arrivals) > self.queue_cap:
            room = max(self.queue_cap - len(self.queue), 0)
            # reject lowest-tier-first: latency-critical arrivals take
            # the remaining room before any lower tier is admitted
            # (stable sort keeps FIFO order within a tier)
            ranked = sorted(arrivals, key=lambda r: -r.priority)
            self.queue.extend(ranked[:room])
            self._backpressure(ranked[room:], now)
        else:
            self.queue.extend(arrivals)
        # brownout level: slab occupancy + queue-delay EWMA watermarks
        # with hysteresis; a drain_site transfer window forces at least
        # ``transfer_degrade_level`` for its duration
        level = 0
        if self.brownout is not None:
            # arrival stamps land inside (now, now+dt), so clamp ages at 0;
            # deferred re-releases keep their original stamp and age truly.
            # Occupancy input: backlog share of the bounded queue when one
            # is configured — the slab's per-tick *peak* saturates at 1.0
            # whenever a single batch fills, which says nothing about
            # sustained overload — else the slab share.
            ages = [max(now - r.arrival, 0.0) for r in self.queue]
            delay = float(np.mean(ages)) if ages else 0.0
            occ = (len(self.queue) / self.queue_cap if self.queue_cap > 0
                   else self.slab_pressure())
            level = self.brownout.update(now, occ, delay)
        if now < self.degrade_until:
            level = max(level, self.transfer_degrade_level)
        self._level = level
        self._police_queue(now)
        # per-replica service capacity this tick (mu * dt, M/M/1 analog —
        # doubling replicas doubles capacity, the paper's 16->32 threads).
        # The fractional part carries across ticks so mu*dt < 1 meters
        # slowly instead of truncating to a permanently stalled queue.
        self._budget_frac += self.service_rate * dt
        budget = int(self._budget_frac)
        self._budget_frac -= budget
        cap = self._degrade_cap() if level >= 1 else 0
        tokens_before = self.total_tokens
        for name in sorted(self.registries):
            reg = self.registries[name]
            if not self._node_reachable(name):
                # partitioned replica: the frontend can't route to it nor
                # observe its output — freeze it (no metering, no pump)
                # until the lifecycle controller re-serves its work
                # elsewhere and the rejoining node is epoch-fenced
                reg.gauge("ersap_queue_len").set(len(self.queue))
                continue
            allow = -1
            if self.breaker is not None:
                allow = self.breaker.allow(name, now)
                if allow == 0:
                    # ejected replica: route around it entirely until the
                    # cool-off elapses and probe traffic passes
                    reg.gauge("ersap_queue_len").set(len(self.queue))
                    continue
            n_take = min(len(self.queue), budget)
            if allow >= 0:
                n_take = min(n_take, allow)       # half-open: probes only
            took, self.queue = self.queue[:n_take], self.queue[n_take:]
            if took:
                # queue-wait distribution: time each request spent in the
                # FIFO before reaching a replica (deferred retries age too)
                h = self.metrics.histogram("ersap_queue_wait_s")
                for r in took:
                    h.observe(max(now - r.arrival, 0.0))
            if self.breaker is not None and allow >= 0:
                self.breaker.note_probe(name, len(took))
            if cap:
                # polite degradation: cap generation length before
                # dropping anyone (greedy decode is deterministic in the
                # prompt, so capped output is a prefix of the full one)
                took = [replace(r, max_new=min(r.max_new, cap))
                        if r.max_new > cap else r for r in took]
            rt = self.runtimes.get(name)
            if rt is not None:
                rt.reset_pressure()    # per-tick slab-pressure window
                rt.sim_now = now       # runtime spans carry sim-time
                rt.spec_enabled = (level == 0)
            st0 = self.stats.get(name)
            tokens0 = st0.tokens if st0 is not None else 0
            self._process(took, name, now)
            if self.breaker is not None:
                st1 = self.stats.get(name)
                self.breaker.observe(
                    name, now, (st1.tokens if st1 is not None else 0)
                    - tokens0, had_work=bool(took))
            reg.gauge("ersap_queue_len").set(len(self.queue))
            reg.gauge("ersap_brownout_level").set(level)
            rt = self.runtimes.get(name)
            if rt is not None:
                # slab pressure, both layouts: busy slots always (the
                # dense path's only exhaustible resource), plus held KV
                # pages when paged (pool high-water mark is the
                # capacity-planning signal for sizing pool_pages). Both
                # feed the HPA/twin memory-pressure input (slab_pressure)
                # and scrape the per-tick *peak* — pump() runs to
                # quiescence, so the instantaneous value here is 0.
                reg.gauge("ersap_slab_slots_used").set(rt.peak_slots)
                # the per-tick peaks also land in histograms so the
                # HPA/twin and the exporter see the *distribution* of
                # occupancy peaks, not the last-write-wins gauge value
                reg.histogram("ersap_slab_slots_peak",
                              buckets=COUNT_BUCKETS).observe(rt.peak_slots)
                if rt.kernels.rcfg.paged:
                    reg.gauge("ersap_kv_pages").set(rt.peak_pages)
                    reg.histogram("ersap_kv_pages_peak",
                                  buckets=COUNT_BUCKETS).observe(
                                      rt.peak_pages)
                    reg.gauge("ersap_pages_hwm").set(rt.pages_hwm)
                # prefix-cache / speculative-decode effectiveness gauges
                # (cumulative hit count + live shared pages; accept rate
                # over all drafts so far) — scraped alongside pool
                # occupancy so capacity dashboards see both how much HBM
                # sharing is saving and how much verify bandwidth the
                # drafter converts into emitted tokens
                if rt.kernels.rcfg.prefix_cache:
                    reg.gauge("ersap_prefix_hits").set(rt.prefix_hits)
                    reg.gauge("ersap_shared_pages").set(rt.shared_pages)
                if rt.kernels.rcfg.spec_decode:
                    reg.gauge("ersap_spec_accept_rate").set(
                        rt.spec_accept_rate)
        self.tokens_rate = (self.total_tokens - tokens_before) / max(dt, 1e-9)
        self.metrics.gauge("ersap_queue_len").set(len(self.queue))
        self.metrics.gauge("ersap_brownout_level").set(level)
        self.prom.scrape(now)
        self.history.append((now, len(self.queue), self.serving.replicas,
                             self.control))
        return len(self.queue)

    def slab_pressure(self) -> float:
        """Mean per-replica slab occupancy in [0, 1] (paged: page-pool
        share; dense: busy-slot share) — the memory-pressure signal the
        multi-signal HPA and the twin's priority escalation consume.
        The mean (not max) so the control loop converges: a scale-up
        adds empty slabs and visibly lowers the signal, whereas one
        pinned hot replica under a max would keep proposing more
        replicas that cannot relieve it (its KV does not migrate)."""
        if not self.runtimes:
            return 0.0
        return sum(rt.occupancy for rt in self.runtimes.values()) / \
            len(self.runtimes)

    def _process(self, requests: List[Request], replica: str, now: float):
        """Serve ``requests`` on ``replica``: slot-slab continuous batching
        when available, legacy chunked prefill+decode otherwise."""
        if not requests:
            rt = self._refresh_runtime(replica)
            if rt is not None and rt.inflight:
                for fin in rt.pump():       # restored in-flight work
                    self._finish(replica, fin.req, fin.tokens, now)
            return
        rt = self._refresh_runtime(replica)
        if rt is None:
            for j in range(0, len(requests), self.max_batch):
                self._process_chunked(requests[j:j + self.max_batch],
                                      replica, now)
            return
        fitting = [r for r in requests if rt.fits(r)]
        oversize = [r for r in requests if not rt.fits(r)]
        bounced = rt.submit(fitting)
        if bounced:
            # the runtime's bounded pending queue pushed back — return
            # the overflow to the source with retry-after (never dropped
            # silently, never queued unboundedly)
            self._backpressure(bounced, now)
        for fin in rt.pump():
            self._finish(replica, fin.req, fin.tokens, now)
        for j in range(0, len(oversize), self.max_batch):
            self._process_chunked(oversize[j:j + self.max_batch],
                                  replica, now)

    def _finish(self, replica: str, req: Request, n_tokens: int, now: float):
        reg = self.registries[replica]
        st = self.stats[replica]
        st.served += 1
        st.tokens += n_tokens
        self.total_served += 1
        self.total_tokens += n_tokens
        reg.counter("ersap_served_total").inc(1)
        reg.counter("ersap_tokens_total").inc(n_tokens)
        lat = max(now - req.arrival, 0.0)
        reg.histogram("ersap_latency_s").observe(lat)
        reg.histogram("ersap_per_token_s").observe(lat / max(n_tokens, 1))
        self.completed.append((req.rid, now))
        self._drain_t.pop(req.rid, None)
        if self.tracer is not None:
            self.tracer.span("retire", now, rid=req.rid, replica=replica,
                             tokens=n_tokens)
        if self.recorder is not None:
            self.recorder.note_latency(now, lat, req.priority)
            self.recorder.note_served(now)

    def _process_chunked(self, requests: List[Request], replica: str,
                         now: float):
        """Pre-PR path (kept for recurrent families + oversize requests):
        one prefill per chunk shape, Python-loop decode, every request
        over-decoded to the chunk's max_new."""
        if not requests:
            return
        B = len(requests)
        plen = requests[0].prompt_len
        rng = np.random.default_rng(int(now * 1000) % (2**31))
        toks = rng.integers(0, self.cfg.vocab, (B, plen)).astype(np.int32)
        logits, cache = self.serving.prefill_fn(self.serving.params, toks)
        cache = MA.grow_cache(self.cfg, cache,
                              plen + (self.cfg.n_meta_tokens or 0)
                              + max(r.max_new for r in requests) + 1)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        n_new = max(r.max_new for r in requests)
        for _ in range(n_new):
            logits, cache = self.serving.decode_fn(self.serving.params, tok,
                                                   cache)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        for r in requests:
            self._finish(replica, r, n_new, now)

    # ---------------------------------------------------- observability
    def _wire_plane_obs(self) -> None:
        """Propagate the engine's tracer/profiler into the control plane
        (idempotent; called whenever the plane might be fresh)."""
        if self.plane is None:
            return
        if self.tracer is not None:
            if getattr(self.plane, "tracer", None) is None:
                self.plane.tracer = self.tracer
            if getattr(self.plane.scheduler, "tracer", None) is None:
                self.plane.scheduler.tracer = self.tracer
            if getattr(self.plane.nodes, "tracer", None) is None:
                self.plane.nodes.tracer = self.tracer
        if self.profiler is not None and \
                getattr(self.plane, "profiler", None) is None:
            self.plane.profiler = self.profiler

    def enable_observability(self, tracer=None, recorder=None,
                             profiler=None) -> None:
        """Wire the observability plane through every layer: request
        source (enqueue spans), QoS machines (brownout/breaker spans),
        control plane + scheduler + lifecycle controller (schedule/
        preempt/checkpoint/drain spans, tick phase profile), and every
        live runtime (admit spans, host-clock regions, TTFT).
        Safe to call before or after ``deploy``; later-built runtimes
        and planes inherit via ``_make_runtime`` / ``_ensure_plane``."""
        if tracer is not None:
            self.tracer = tracer
            self.source.tracer = tracer
            if self.brownout is not None:
                self.brownout.tracer = tracer
            if self.breaker is not None:
                self.breaker.tracer = tracer
        if recorder is not None:
            self.recorder = recorder
        if profiler is not None:
            self.profiler = profiler
        self._wire_plane_obs()
        for name, rt in self.runtimes.items():
            rt.name = name
            if tracer is not None:
                rt.tracer = tracer

    def exposition(self) -> str:
        """Prometheus text exposition of the whole metric pipeline: the
        engine registry (pod label ``_engine``) plus every per-replica
        registry (``serve.py --metrics-out``)."""
        return render_exposition({"_engine": self.metrics,
                                  **self.registries})

    # ---------------------------------------------------------- control
    def control_step(self, now: float):
        """Assimilate queue depth into the twin; the twin policy and the
        reactive HPA are *spec writers* on the Deployment — desired
        replicas, and (twin path) the priority class, Fig. 8's control
        regions extended to a (replicas, priority) action space. The
        slab-pressure gauge feeds both: the multi-signal HPA as its
        memory signal, the twin as a priority-escalation trigger. The
        controllers/scheduler converge the pod set — escalated serving
        preempts batch work instead of queueing behind it."""
        qlen = max(len(self.queue), 1e-3)
        self.twin.assimilate(qlen, self.control)
        occupancy = self.slab_pressure()
        pclass = None
        if self.use_twin:
            self.control, pclass = self.policy.recommend_action(
                self.twin, self.control, now, occupancy=occupancy)
            desired = replicas_for_control(self.control, self.base_replicas)
            source = "digital-twin"
        else:
            sig = PressureSignals(queue_depth=len(self.queue),
                                  tokens_per_s=self.tokens_rate,
                                  slab_occupancy=occupancy)
            desired = self.hpa.evaluate_signals(
                max(len(self.pods), 1), sig, now)
            source = "hpa"
        # the Deployment spec may exceed the mesh's device budget (pods
        # are simulated serving replicas; scale_to clamps the actual
        # mesh build to max_replicas itself)
        desired = max(1, desired)
        if min(desired, self.serving.max_replicas()) != self.serving.replicas:
            self.serving.scale_to(desired, now)
        if self.cluster is not None and DEPLOYMENT in self.cluster.deployments:
            if pclass is not None:
                self.cluster.set_priority(DEPLOYMENT, pclass, now,
                                          source=source)
            self.cluster.scale(DEPLOYMENT, desired, now, source=source)
            self.reconcile(now)
        return desired
