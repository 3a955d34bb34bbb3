"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

Device operations are read from each ``/device:TPU:<n>`` plane's
``XLA Ops`` line, each charged to the program (``XLA Modules`` event,
``jit_<function>(<fingerprint>)``) that holds it. The line nests: a
``while`` operation spans the operations of its body, which is why busy
time is a union and kernel time is summed by the kernel's own name. On the CPU, where XLA runs its operations on host threads, an
event that carries an ``hlo_module`` statistic is a device operation of
chip 0; this is how the tests record a trace without a chip. Host spans
are the harness's own ``jax.profiler.TraceAnnotation`` names (``bench.*``).
The traced window is the harness's ``bench.traced`` span.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import warnings
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all", re.I)
WINDOW_SPAN = "bench.traced"
CONTROL = re.compile(r"^(while|conditional|call)(\.|$)")


def op_name(text: str) -> str:
    """The HLO instruction's name: the TPU trace names an operation by its
    whole HLO text (``%fusion.12 = bf16[...] fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


@dataclass
class Op:
    chip: int
    name: str
    module: str
    start: float        # seconds, on the trace's clock
    dur: float


@dataclass
class Trace:
    ops: List[Op]
    spans: List[Tuple[str, float, float]]      # (name, start, end)
    window: Tuple[float, float]
    chips: List[int] = field(default_factory=list)
    modules: List[Op] = field(default_factory=list)   # program executions

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def _clip(self, a, b):
        return max(a, self.window[0]), min(b, self.window[1])

    def intervals(self, chip: int, keep=None) -> List[Tuple[float, float]]:
        """Union of the chip's operation intervals inside the window (of
        the operations ``keep`` accepts), merged and sorted."""
        iv = sorted(self._clip(o.start, o.start + o.dur)
                    for o in self.ops
                    if o.chip == chip and (keep is None or keep(o)))
        out: List[List[float]] = []
        for a, b in iv:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                out[-1][1] = max(out[-1][1], b)
            else:
                out.append([a, b])
        return [(a, b) for a, b in out]

    def busy_s(self, chip: int) -> float:
        return sum(b - a for a, b in self.intervals(chip))

    def mean_busy_s(self) -> float:
        return sum(self.busy_s(c) for c in self.chips) / len(self.chips)

    def _sum(self, events) -> float:
        t = 0.0
        for o in events:
            a, b = self._clip(o.start, o.start + o.dur)
            t += max(b - a, 0.0)
        return t

    def op_s(self, chip: int, name: str) -> float:
        """Seconds of the chip's operations whose name contains ``name``
        (a kernel), inside the window."""
        return self._sum(o for o in self.ops
                         if o.chip == chip and name in o.name)

    def program_s(self, chip: int, prefix: str) -> float:
        """Seconds the chip spent in programs whose name starts with
        ``prefix`` (``jit_block``: fused decode), inside the window. Where
        the trace has no program events (the CPU), the program's
        operations are summed instead."""
        if self.modules:
            return self._sum(o for o in self.modules
                             if o.chip == chip and o.name.startswith(prefix))
        return self._sum(o for o in self.ops
                         if o.chip == chip and o.module.startswith(prefix))

    def mean(self, fn) -> float:
        return sum(fn(c) for c in self.chips) / len(self.chips)

    def collective_s(self, chip: int) -> float:
        """Seconds in which a collective ran on the chip (a union, so
        overlapping collectives count once)."""
        return sum(b - a for a, b in self.intervals(
            chip, lambda o: COLLECTIVE.search(o.name)))

    def top_ops(self, n: int = 10) -> List[list]:
        """The operations that took most device time, averaged over chips,
        as ``<program>/<op>``. Control-flow operations (``while``,
        ``conditional``, ``call``) hold the operations of their bodies and
        are left out, so nothing counts twice."""
        acc: Dict[str, float] = defaultdict(float)
        for o in self.ops:
            short = op_name(o.name)
            if CONTROL.match(short):
                continue
            a, b = self._clip(o.start, o.start + o.dur)
            acc[f"{o.module.split('(')[0]}/{short}"] += \
                max(b - a, 0.0) / len(self.chips)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def idle_by_host(self, n: int = 10) -> List[list]:
        """Idle device time, averaged over chips, charged to the host span
        that overlaps it (``host.other`` where no harness span does)."""
        spans = sorted((a, b, nm) for nm, a, b in self.spans
                       if nm != WINDOW_SPAN)
        starts = [s[0] for s in spans]
        acc: Dict[str, float] = defaultdict(float)
        for chip in self.chips:
            busy = self.intervals(chip)
            edges = [self.window[0]] + [x for iv in busy for x in iv] \
                + [self.window[1]]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b <= a:
                    continue
                covered = 0.0
                i = max(bisect.bisect_right(starts, a) - 1, 0)
                while i < len(spans) and spans[i][0] < b:
                    sa, sb, nm = spans[i]
                    ov = min(b, sb) - max(a, sa)
                    if ov > 0:
                        acc[nm] += ov / len(self.chips)
                        covered += ov
                    i += 1
                if b - a - covered > 0:
                    acc["host.other"] += (b - a - covered) / len(self.chips)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def _stats(ev) -> dict:
    with warnings.catch_warnings():
        # jaxlib's event_stats type lacks __module__; harmless
        warnings.simplefilter("ignore", DeprecationWarning)
        return {k: v for k, v in ev.stats}


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest one under a directory)."""
    import jax
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                 recursive=True), key=os.path.getmtime)
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    pd = jax.profiler.ProfileData.from_file(path)
    ops: List[Op] = []
    modules: List[Op] = []
    spans: List[Tuple[str, float, float]] = []
    chips = set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            chips.add(chip)
            lines = {ln.name: ln for ln in plane.lines}
            mods = sorted((e.start_ns * 1e-9, (e.start_ns + e.duration_ns)
                           * 1e-9, e.name)
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            mstart = [x[0] for x in mods]
            modules.extend(Op(chip, nm, nm, a, b - a) for a, b, nm in mods)
            for e in (lines["XLA Ops"].events if "XLA Ops" in lines else ()):
                s = e.start_ns * 1e-9
                st = _stats(e)
                mod = str(st.get("hlo_module", ""))
                if not mod:
                    i = bisect.bisect_right(mstart, s) - 1
                    if i >= 0 and mods[i][1] >= s:
                        mod = mods[i][2]
                ops.append(Op(chip, e.name, mod, s, e.duration_ns * 1e-9))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                s, d = e.start_ns * 1e-9, e.duration_ns * 1e-9
                if e.name.startswith("bench."):
                    spans.append((e.name, s, s + d))
                    continue
                st = _stats(e)
                if "hlo_module" in st and d > 0:
                    chips.add(0)
                    ops.append(Op(0, e.name, str(st["hlo_module"]), s, d))
    win = [(a, b) for nm, a, b in spans if nm == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in {path}")
    if not chips:
        raise ValueError(f"no device operations in {path}")
    return Trace(ops, spans, (min(a for a, _ in win), max(b for _, b in win)),
                 sorted(chips), modules)

