"""Request-lifecycle tracing (observability plane, tentpole 1).

Every ``Request`` carries a trace context stamped at the
``RequestSource`` (``Request.trace_id``), and each hop of its life
records a ``Span`` against the shared ``Tracer`` ring with monotonic
sim-time:

    enqueue -> police/shed -> admit (miss/tail/full/follow) -> prefill
    -> decode waves -> retire

plus control-plane spans (``schedule``, ``preempt``, ``drain_node``,
``checkpoint``, ``crash_restore``, ``transfer_window``), per-rid fault
spans (``drain``, ``restore``) and QoS transitions (``brownout``,
``breaker``). A single rid is reconstructable end-to-end across
replicas, sites and fault incarnations: ``Tracer.chain(rid)`` returns
its spans in emission order, and every rid-carrying span is stamped
with the rid's current *incarnation* (bumped whenever a ``restore``
span lands), so "decode on replica A, incarnation 0" and "decode on
replica B, incarnation 1" are distinguishable after a drain.

Two clocks. ``t`` is the engine's sim-time, which orders a rid's life
across replicas. ``wall`` is ``time.perf_counter()`` at emission (or at
the start of a region), the host clock a serving harness times its
requests on. ``Tracer.region`` times a stretch of host work: one span
with ``wall`` and ``dur``, and the same interval as a
``jax.profiler.TraceAnnotation`` named ``runtime.<name>``, so the ring
and a profiler trace line up on one clock. The runtime's regions are
``admit_plan``, ``prefill``, ``first_token``, ``decode``,
``decode_read``, ``harvest`` and ``pages_commit``
(``repro.streaming.runtime``).

Cost model: tracing must be cheap enough to leave on (<5% tokens/s —
``bench_observability_overhead``). ``Tracer.span`` early-returns when
disabled, block-level spans (``prefill``/``decode``) carry a tuple of
rids instead of one span per request per wave, and the ring is a
bounded ``deque`` — memory is O(cap), never O(run length). Producers
hold an optional ``tracer`` attribute defaulting to ``None`` and guard
every emission with one attribute test, so the disabled path costs a
single ``is None`` branch.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List


@dataclass
class Span:
    """One hop of a request's (or the control plane's) life.

    ``seq`` is a tracer-global monotonic counter: spans emitted at the
    same sim-time (one tick) still order exactly as they happened.
    ``inc`` is the rid's fault incarnation at emission time (0 before
    any restore). Block-level spans (prefill/decode) use ``rid=0`` and
    list their member rids under ``attrs["rids"]``. ``wall`` is the host
    clock (``time.perf_counter``) at emission or at a region's start;
    ``dur`` is a region's length on it (0 for a point span)."""
    name: str
    t: float
    rid: int = 0
    seq: int = 0
    inc: int = 0
    attrs: Dict[str, object] = field(default_factory=dict)
    wall: float = 0.0
    dur: float = 0.0

    @property
    def end(self) -> float:
        return self.wall + self.dur

    def to_dict(self) -> dict:
        attrs = {k: (list(v) if isinstance(v, tuple) else v)
                 for k, v in self.attrs.items()}
        return {"name": self.name, "t": self.t, "rid": self.rid,
                "seq": self.seq, "inc": self.inc, "attrs": attrs,
                "wall": self.wall, "dur": self.dur}


class _Region:
    """Context of one ``Tracer.region``: the span is in the ring from the
    start (emission order is start order); ``dur`` is filled in at exit.
    Entering yields the span, so a producer can add attributes it only
    knows at the end."""
    __slots__ = ("span", "_note")

    def __init__(self, span: Span):
        self.span = span
        self._note = _annotation("runtime." + span.name)

    def __enter__(self) -> Span:
        self._note.__enter__()
        return self.span

    def __exit__(self, *exc) -> None:
        self.span.dur = time.perf_counter() - self.span.wall
        self._note.__exit__(*exc)


class _NullRegion:
    """What a disabled tracer's ``region`` returns: enters to ``None``."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc) -> None:
        return None


NULL_REGION = _NullRegion()


def _annotation(name: str):
    """``jax.profiler.TraceAnnotation``, imported on first use so that the
    control plane imports without JAX."""
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(name)


class Tracer:
    """Bounded span ring shared by every layer of the stack.

    One Tracer per engine/driver; producers (source, engine, runtimes,
    scheduler, controllers, QoS machines) all write here so ``chain``
    sees a rid's whole life regardless of which replica or site served
    each hop."""

    def __init__(self, enabled: bool = True, cap: int = 65536):
        self.enabled = enabled
        self.cap = cap
        self.spans: deque = deque(maxlen=cap)
        self.dropped = 0                      # spans evicted by the ring
        # rid -> restore count: the fault-incarnation stamp. A rid's
        # incarnation bumps when a ``restore`` span lands for it, so
        # post-restore spans carry inc = (restores seen so far).
        self.incarnations: Dict[int, int] = {}
        self._seq = 0

    def span(self, name: str, t: float, rid: int = 0, **attrs) -> None:
        if not self.enabled:
            return
        self._emit(name, t, rid, attrs)

    def region(self, name: str, t: float = 0.0, rid: int = 0, **attrs):
        """Context manager timing a stretch of host work: one span with
        ``wall`` at entry and ``dur`` at exit, and a
        ``jax.profiler.TraceAnnotation("runtime." + name)`` over the same
        interval. Enters to the span (``None`` when disabled)."""
        if not self.enabled:
            return NULL_REGION
        return _Region(self._emit(name, t, rid, attrs))

    def _emit(self, name: str, t: float, rid: int, attrs: dict) -> Span:
        if rid and name == "restore":
            self.incarnations[rid] = self.incarnations.get(rid, 0) + 1
        self._seq += 1
        if len(self.spans) == self.cap:
            self.dropped += 1
        sp = Span(name, float(t), int(rid), self._seq,
                  self.incarnations.get(rid, 0) if rid else 0, attrs,
                  time.perf_counter())
        self.spans.append(sp)
        return sp

    def chain(self, rid: int) -> List[Span]:
        """Every span of one rid, in emission order: spans stamped with
        the rid directly plus block-level spans listing it in
        ``attrs["rids"]``."""
        out = []
        for s in self.spans:
            if s.rid == rid or rid in (s.attrs.get("rids") or ()):
                out.append(s)
        return out

    def rids(self) -> List[int]:
        seen = set()
        for s in self.spans:
            if s.rid:
                seen.add(s.rid)
            seen.update(s.attrs.get("rids") or ())
        return sorted(seen)

    def dump(self) -> List[dict]:
        """JSON-safe snapshot of the ring (flight-recorder bundles)."""
        return [s.to_dict() for s in self.spans]


#: Shared disabled tracer: safe default for call sites that want to
#: emit unconditionally (``NULL_TRACER.span(...)`` is a no-op).
NULL_TRACER = Tracer(enabled=False)
