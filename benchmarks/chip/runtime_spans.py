"""The runtime's own spans (``repro.core.tracing``), read beside a run's
record (``run_cell.Record``).

``DecodeRuntime`` times its host work in regions of a ``Tracer`` ring:
``admit_plan``, ``prefill``, ``first_token``, ``decode``, ``decode_read``,
``harvest`` and ``pages_commit``. A span's ``wall`` is
``time.perf_counter()``, the clock the harness times requests on, so a
span compares directly with a request's ``due`` and ``first``. In a
profiler trace the same regions are host events named ``runtime.<name>``
on the trace's clock (``host_spans``).

Each function returns None where it finds nothing to read, or where the
ring dropped spans and so may have lost some.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

HOST_PREFIX = "runtime."
OTHER = "host.other"


def _by_rid(tracer, name: str) -> Dict[int, object]:
    """rid -> the first span named ``name`` that lists it."""
    out: Dict[int, object] = {}
    for s in tracer.spans:
        if s.name != name:
            continue
        for rid in (s.attrs.get("rids") or (s.rid,)):
            out.setdefault(rid, s)
    return out


def _judged(rec):
    return [rec.reqs[rid] for rid in rec.in_window]


def admit_wait_p90(tracer, rec) -> Optional[float]:
    """90th percentile, over the judged requests, of the start of the
    ``prefill`` region that admitted each minus when it was due."""
    if tracer is None or tracer.dropped:
        return None
    pre = _by_rid(tracer, "prefill")
    return rec.q([pre[r.rid].wall - r.due for r in _judged(rec)
                  if r.rid in pre], 90)


def first_token_hold_p90(tracer, rec) -> Optional[float]:
    """90th percentile, over the judged requests, of the end of the step
    that delivered each first token (the harness's ``first``) minus the
    end of the ``first_token`` region, when that token reached the
    host."""
    if tracer is None or tracer.dropped:
        return None
    ft = _by_rid(tracer, "first_token")
    return rec.q([r.first - ft[r.rid].end for r in _judged(rec)
                  if r.rid in ft and r.first is not None], 90)


def dead_row_steps(tracer, rec) -> Optional[float]:
    """Share, in %, of the row-steps the fused decode blocks computed that
    delivered no token: 1 - sum(live_row_steps) / sum(steps x rows) over
    the ``decode`` regions that ended in the window."""
    if tracer is None or tracer.dropped:
        return None
    w0, w1 = rec.window
    live = total = 0
    for s in tracer.spans:
        if s.name == "decode" and "rows" in s.attrs and w0 < s.end <= w1:
            live += s.attrs["live_row_steps"]
            total += s.attrs["steps"] * s.attrs["rows"]
    return 100.0 * (1.0 - live / total) if total else None


def order_faults(tracer, rec) -> Optional[List[int]]:
    """Judged rids whose times break due <= admission (the ``admit``
    span) <= first token on the host (end of ``first_token``) <= the
    harness's ``first``."""
    if tracer is None or tracer.dropped:
        return None
    adm = _by_rid(tracer, "admit")
    ft = _by_rid(tracer, "first_token")
    bad = []
    for r in _judged(rec):
        a, f = adm.get(r.rid), ft.get(r.rid)
        if a is None or f is None or r.first is None or not (
                r.due <= a.wall <= f.end <= r.first):
            bad.append(r.rid)
    return bad


def host_spans(path: str) -> List[Tuple[str, float, float]]:
    """(name, start, end) of the host events named ``runtime.*`` in one
    ``.xplane.pb`` (or the newest under a directory), in seconds on the
    trace's clock."""
    import jax
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True), key=os.path.getmtime)[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(HOST_PREFIX):
                    s = e.start_ns * 1e-9
                    out.append((e.name, s, s + e.duration_ns * 1e-9))
    return out


def innermost(spans, lo: float, hi: float) -> List[Tuple[float, float, str]]:
    """[lo, hi) cut into pieces, each named by the innermost span over it
    (the latest started, then the earliest ended), or ``host.other``."""
    cuts = sorted({lo, hi} | {x for _, a, b in spans for x in (a, b)
                              if lo < x < hi})
    out: List[Tuple[float, float, str]] = []
    for a, b in zip(cuts, cuts[1:]):
        over = [(sa, -sb, nm) for nm, sa, sb in spans if sa <= a and sb >= b]
        nm = max(over)[2] if over else OTHER
        if out and out[-1][2] == nm and out[-1][1] == a:
            out[-1] = (out[-1][0], b, nm)
        else:
            out.append((a, b, nm))
    return out


def idle_by_innermost(trace, spans) -> List[list]:
    """Idle device time inside the traced window, averaged over chips,
    each idle stretch charged once, piece by piece, to the innermost host
    span over it (``host.other`` where none is). The charges sum to the
    idle time. ``spans`` are (name, start, end) on the trace's clock."""
    lo, hi = trace.window
    pieces = innermost([s for s in spans if s[0] != "bench.traced"], lo, hi)
    starts = [p[0] for p in pieces]
    acc: Dict[str, float] = defaultdict(float)
    for chip in trace.chips:
        busy = trace.intervals(chip)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            i = max(bisect.bisect_right(starts, a) - 1, 0)
            while i < len(pieces) and pieces[i][0] < b:
                pa, pb, nm = pieces[i]
                ov = min(b, pb) - max(a, pa)
                if ov > 0:
                    acc[nm] += ov / len(trace.chips)
                i += 1
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])]
