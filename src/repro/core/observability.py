"""Unified observability plane: Prometheus text exposition over the
§4.6 registries, a tick phase profiler, and an SLO flight recorder.

Three pieces (tentpole 2-4 of the observability PR):

- ``render_exposition`` / ``parse_exposition``: the §4.6 ``Registry``
  contents as Prometheus text exposition format — ``# TYPE`` comments,
  ``pod`` labels, full cumulative ``_bucket{le=...}`` series for
  histograms. ``serve.py --metrics-out`` dumps it; ``tools/metriclint.py``
  and the obs-smoke CI job parse it back.
- ``TickProfiler``: cheap phase-timing accumulator for the control-plane
  tick (nodes reconcile, deployment reconcile, scheduler place, audit).
  Surfaced per bench in ``BENCH_*.json`` and by ``serve.py`` at end of
  run.
- ``FlightRecorder``: bounded ring of recent events riding the span
  ring, with burn-rate SLO tracking over a sliding window (LC p99
  latency, shed fraction, restore latency). A threshold breach or an
  ``InvariantAuditor`` violation trips an *incident*: a JSON bundle of
  the recent spans/events (``tools/tracedump.py`` renders a timeline),
  auto-written to ``dump_dir`` when configured.
"""
from __future__ import annotations

import json
import math
import pathlib
import time
from collections import deque
from contextlib import contextmanager
from dataclasses import asdict, dataclass
from typing import Dict, List, Optional

from repro.core.metrics import Counter, Gauge, Histogram, Registry, \
    split_series

# --------------------------------------------------------- exposition

def _fmt(v: float) -> str:
    if isinstance(v, float) and math.isinf(v):
        return "+Inf" if v > 0 else "-Inf"
    return format(float(v), ".10g")


def _merge_labels(pod: str, lbl: str, extra: str = "") -> str:
    """Combine the pod label, a metric's own rendered label block (the
    ``{k="v"}`` suffix of its series key) and an optional extra pair."""
    inner = f'pod="{pod}"'
    if lbl:
        inner += "," + lbl[1:-1]
    if extra:
        inner += "," + extra
    return "{" + inner + "}"


def render_exposition(registries: Dict[str, Registry]) -> str:
    """Prometheus text exposition of every registry, keyed by pod name.

    Histograms render the full cumulative bucket series (``_bucket`` with
    ``le`` labels) plus ``_sum``/``_count`` — the distribution the plain
    ``Registry.collect`` scrape flattens away."""
    groups: Dict[str, list] = {}          # base name -> [(type, line), ...]
    for pod in sorted(registries):
        reg = registries[pod]
        for key in sorted(reg.metrics):
            m = reg.metrics[key]
            base, lbl = split_series(key)
            if isinstance(m, Histogram):
                lines = groups.setdefault(base, [("histogram", None)])
                acc = 0
                for bound, cnt in zip(m.buckets, m.counts):
                    acc += cnt
                    lines.append((None, f"{base}_bucket"
                                  f"{_merge_labels(pod, lbl, f'le={json.dumps(_fmt(bound))}')}"
                                  f" {acc}"))
                lines.append((None, f"{base}_sum{_merge_labels(pod, lbl)}"
                              f" {_fmt(m.total)}"))
                lines.append((None, f"{base}_count{_merge_labels(pod, lbl)}"
                              f" {m.n}"))
            else:
                kind = "counter" if isinstance(m, Counter) else "gauge"
                lines = groups.setdefault(base, [(kind, None)])
                lines.append((None, f"{base}{_merge_labels(pod, lbl)}"
                              f" {_fmt(m.value)}"))
    out = []
    for base in sorted(groups):
        kind = groups[base][0][0]
        out.append(f"# TYPE {base} {kind}")
        out.extend(line for _, line in groups[base][1:])
    return "\n".join(out) + ("\n" if out else "")


def parse_exposition(text: str) -> Dict[str, float]:
    """Strict-enough parser for the exposition format above: returns
    {series-with-labels: value}; raises ValueError on a malformed line.
    Used by metriclint / the obs-smoke job to assert the dump parses."""
    out: Dict[str, float] = {}
    for i, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        # series name [+ one balanced label block], one space, a float
        j = line.find("{")
        if j >= 0:
            k = line.find("}")
            if k < j:
                raise ValueError(f"line {i}: unbalanced labels: {line!r}")
            name, rest = line[:k + 1], line[k + 1:]
        else:
            parts = line.split(" ", 1)
            if len(parts) != 2:
                raise ValueError(f"line {i}: not 'name value': {line!r}")
            name, rest = parts
        try:
            val = float(rest.strip().replace("+Inf", "inf"))
        except ValueError:
            raise ValueError(f"line {i}: bad value in {line!r}")
        if not name or not (name[0].isalpha() or name[0] == "_"):
            raise ValueError(f"line {i}: bad series name {name!r}")
        out[name] = val
    return out


# ----------------------------------------------------------- profiler

class TickProfiler:
    """Phase-timing accumulator (wall-clock, ``time.perf_counter``).

    Phases are plain string names; nesting is allowed and simply counts
    the inner phase inside the outer one. The control plane's tick phases
    use it; the runtime times its host work with ``Tracer.region``."""

    def __init__(self):
        self.phases: Dict[str, list] = {}      # name -> [calls, total_s]

    def add(self, name: str, dt: float) -> None:
        e = self.phases.get(name)
        if e is None:
            self.phases[name] = [1, dt]
        else:
            e[0] += 1
            e[1] += dt

    @contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def summary(self) -> Dict[str, dict]:
        return {k: {"calls": c, "total_s": round(t, 6),
                    "mean_us": round(t / c * 1e6, 1)}
                for k, (c, t) in sorted(self.phases.items())}


# ----------------------------------------------------- flight recorder

@dataclass
class SLOConfig:
    """Burn-rate SLO thresholds over a sliding ``window_s`` window.
    A threshold of 0 disables that objective."""
    lc_p99_s: float = 0.0        # p99 completion latency, LC tier only
    shed_frac: float = 0.0       # shed / (shed + served) fraction
    restore_s: float = 0.0       # max drain -> restore latency
    window_s: float = 300.0
    min_samples: int = 16        # latency samples needed before judging
    cooldown_s: float = 120.0    # min sim-time between trips per reason


class FlightRecorder:
    """Bounded ring of recent spans/events + burn-rate SLO tracking.

    The engine feeds it per-request outcomes (``note_latency`` /
    ``note_shed`` / ``note_served`` / ``note_restore``); the driver
    calls ``check(now)`` once per tick. When a burn rate crosses its
    SLO threshold — or ``trip`` is called directly (the
    ``InvariantAuditor`` does, before raising) — an incident bundle of
    the recent spans and events is recorded and, when ``dump_dir`` is
    set, written as JSON for ``tools/tracedump.py``."""

    def __init__(self, tracer=None, slo: Optional[SLOConfig] = None,
                 dump_dir: Optional[str] = None, cap: int = 4096):
        self.tracer = tracer
        self.slo = slo or SLOConfig()
        self.dump_dir = dump_dir
        self.events: deque = deque(maxlen=cap)      # (t, kind, detail)
        self.incidents: List[dict] = []
        self._lat: deque = deque()                  # (t, latency, priority)
        self._served: deque = deque()               # t
        self._shed: deque = deque()                 # t
        self._restores: deque = deque()             # (t, duration)
        self._last_trip: Dict[str, float] = {}

    # ------------------------------------------------------ ingestion
    def event(self, now: float, kind: str, detail: str = "") -> None:
        self.events.append((float(now), kind, detail))

    def note_latency(self, now: float, latency_s: float,
                     priority: int = 10) -> None:
        self._lat.append((float(now), float(latency_s), int(priority)))

    def note_served(self, now: float) -> None:
        self._served.append(float(now))

    def note_shed(self, now: float) -> None:
        self._shed.append(float(now))

    def note_restore(self, now: float, duration_s: float) -> None:
        self._restores.append((float(now), float(duration_s)))

    def _trim(self, now: float) -> None:
        lo = now - self.slo.window_s
        for dq in (self._served, self._shed):
            while dq and dq[0] < lo:
                dq.popleft()
        for dq in (self._lat, self._restores):
            while dq and dq[0][0] < lo:
                dq.popleft()

    # ------------------------------------------------------ burn rates
    def burn(self, now: float) -> Dict[str, float]:
        """Current burn rates over the sliding window."""
        self._trim(now)
        lc = sorted(v for _, v, p in self._lat if p >= 100)
        allv = sorted(v for _, v, _ in self._lat)
        denom = len(self._served) + len(self._shed)
        return {
            "lc_p99_s": lc[min(int(0.99 * len(lc)), len(lc) - 1)]
            if lc else 0.0,
            "p99_s": allv[min(int(0.99 * len(allv)), len(allv) - 1)]
            if allv else 0.0,
            "lc_samples": len(lc),
            "samples": len(allv),
            "shed_frac": len(self._shed) / denom if denom else 0.0,
            "restore_max_s": max((d for _, d in self._restores),
                                 default=0.0),
        }

    def check(self, now: float) -> Optional[dict]:
        """Evaluate SLOs; trip (at most one incident per call, rate
        limited per reason) when a burn rate crosses its threshold."""
        b = self.burn(now)
        slo = self.slo
        if slo.lc_p99_s > 0 and b["lc_samples"] >= slo.min_samples \
                and b["lc_p99_s"] > slo.lc_p99_s:
            return self._maybe_trip(now, "lc-p99",
                                    f"{b['lc_p99_s']:.3f}s > "
                                    f"{slo.lc_p99_s:.3f}s", b)
        if slo.shed_frac > 0 and b["samples"] >= slo.min_samples \
                and b["shed_frac"] > slo.shed_frac:
            return self._maybe_trip(now, "shed-fraction",
                                    f"{b['shed_frac']:.3f} > "
                                    f"{slo.shed_frac:.3f}", b)
        if slo.restore_s > 0 and b["restore_max_s"] > slo.restore_s:
            return self._maybe_trip(now, "restore-latency",
                                    f"{b['restore_max_s']:.3f}s > "
                                    f"{slo.restore_s:.3f}s", b)
        return None

    def _maybe_trip(self, now: float, reason: str, detail: str,
                    burn: dict) -> Optional[dict]:
        last = self._last_trip.get(reason)
        if last is not None and now - last < self.slo.cooldown_s:
            return None
        return self.trip(now, reason, detail, burn)

    # -------------------------------------------------------- incidents
    def trip(self, now: float, reason: str, detail: str = "",
             burn: Optional[dict] = None) -> dict:
        """Record an incident bundle (and write it to ``dump_dir``)."""
        self._last_trip[reason] = now
        self.event(now, "incident", f"{reason}: {detail}")
        bundle = {
            "reason": reason,
            "detail": detail,
            "t": float(now),
            "slo": asdict(self.slo),
            "burn": burn or self.burn(now),
            "events": [list(e) for e in self.events],
            "spans": self.tracer.dump() if self.tracer is not None else [],
        }
        self.incidents.append(bundle)
        if self.dump_dir:
            d = pathlib.Path(self.dump_dir)
            d.mkdir(parents=True, exist_ok=True)
            path = d / f"incident_{len(self.incidents):03d}_{reason}.json"
            path.write_text(json.dumps(bundle, indent=1))
        return bundle

    def dump(self) -> dict:
        """Full JSON-safe flight-recorder state (``serve.py
        --trace-out``): the span ring, recent events, burn rates and
        incident metadata (incident bundles carry their own spans)."""
        return {
            "spans": self.tracer.dump() if self.tracer is not None else [],
            "events": [list(e) for e in self.events],
            "slo": asdict(self.slo),
            "incidents": [{"reason": i["reason"], "detail": i["detail"],
                           "t": i["t"]} for i in self.incidents],
        }
