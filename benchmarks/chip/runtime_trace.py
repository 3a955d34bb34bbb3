#!/usr/bin/env python3
"""The runtime's own spans on a cell's traffic, and what keeping them costs.

    python3 benchmarks/chip/runtime_trace.py --workload <cell> \
        --seeds 1,2,3 --seconds <s> [--profile-seed <n>]

One process; the programs are built once. For each seed the cell's traffic
runs twice through the timed path of ``run_cell.py``, with the profiler
off: once as the benchmark runs it, once with a ``Tracer`` attached to the
runtime after the warm-up (the order alternates from seed to seed). One
JSON line a seed: the end-to-end metrics of both runs, and what the ring
of the traced run reads (``runtime_spans.py``). With ``--profile-seed``
one more run keeps the ring and the profiler over the window's last
``run_cell.TRACE_FOR_S`` seconds, and charges each idle gap of the device
to the innermost ``bench.*`` or ``runtime.*`` span over it. The reference
check is left out. Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import run_cell as RC  # noqa: E402  (adds src/)
from benchmarks.chip import runtime_spans as RS  # noqa: E402


def readings(tracer, rec) -> dict:
    """What the ring reads for one run."""
    bad = RS.order_faults(tracer, rec)
    return {"admit_wait_p90_s": RS.admit_wait_p90(tracer, rec),
            "first_token_hold_p90_s": RS.first_token_hold_p90(tracer, rec),
            "decode_dead_row_steps": RS.dead_row_steps(tracer, rec),
            "order_faults": None if bad is None else len(bad),
            "spans": len(tracer.spans), "dropped": tracer.dropped}


def one_run(cell, seed, seconds, serving, ring: bool, trace_dir=None,
            cfg=None):
    """The seed's weights and a fresh runtime through the cell's traffic,
    its state freed after; the first run (``serving`` None) builds every
    program. Returns (record, tracer or None, serving)."""
    from repro.core.tracing import Tracer
    rep = RC.build(cell, seed, cfg=cfg, serving=serving)
    RC.prime(rep, cell, seed)
    if serving is None:
        RC.warm(rep, RC.reachable(rep.rcfg, cell.mix))
    rep.rt.tracer = Tracer() if ring else None
    rec = RC.drive(rep, cell, seed, seconds, trace_dir)
    tracer = rep.rt.tracer
    RC.free(rep)
    return rec, tracer, rep.serving


def measure(cell, seeds, seconds, profile_seed=None, cfg=None):
    """Yield one result object a seed, then the profiled run's."""
    serving = None
    for k, seed in enumerate(seeds):
        out = {"seed": seed, "order": "off,on" if k % 2 == 0 else "on,off"}
        for ring in ((False, True) if k % 2 == 0 else (True, False)):
            rec, tracer, serving = one_run(cell, seed, seconds, serving,
                                           ring, cfg=cfg)
            tag = "on" if ring else "off"
            out[tag] = {m: v["value"] for m, v in
                        RC.end_to_end(cell, rec, 0.0).items()
                        if m != "setup_s"}
            out[tag]["judged"] = len(rec.in_window)
            out[tag]["unfinished"] = len(rec.unfinished)
            if ring:
                out["ring"] = readings(tracer, rec)
        yield out
    if profile_seed is None:
        return
    from benchmarks.chip import trace as TRC
    trace_dir = tempfile.mkdtemp(prefix="runtime_trace_")
    try:
        rec, tracer, serving = one_run(cell, profile_seed, seconds, serving,
                                       True, trace_dir, cfg=cfg)
        trace = TRC.load(trace_dir)
        spans = trace.spans + RS.host_spans(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    idle = RS.idle_by_innermost(trace, spans)
    yield {"seed": profile_seed, "profiled": True,
           "ring": readings(tracer, rec),
           "window_s": trace.window_s, "busy_s": trace.mean_busy_s(),
           "idle_s": sum(v for _, v in idle), "idle_gaps": idle,
           "idle_gaps_bench_only": trace.idle_by_host(n=20),
           "runtime_spans": sum(1 for s in spans
                                if s[0].startswith(RS.HOST_PREFIX))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--profile-seed", type=int, default=None)
    args = ap.parse_args(argv)
    cell = RC.load_cell(RC.ROOT, args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("runtime_trace: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    for out in measure(cell, seeds, args.seconds, args.profile_seed):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
