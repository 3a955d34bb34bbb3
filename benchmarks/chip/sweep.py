#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest offered rate at which the
queue of requests waiting for admission does not grow over a window.

    python3 benchmarks/chip/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 1.5,2,2.5

One process; each rate runs the cell's ramp and window with a fresh
runtime and prints one JSON line: requests due and finished in the
window, the queue at its start and end, and the tails of those finished.
Run once when a cell is defined; the rate it finds is written into the
mix file. Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run_cell as RC  # noqa: E402  (sets up sys.path)


def queued(rec, t: float) -> int:
    return sum(1 for r in rec.reqs.values()
               if r.due <= t and (r.admit is None or r.admit > t))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = RC.load_cell(RC.ROOT, args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    cell.mix["drain_cap_s"] = 0.0
    rep = None
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell.mix["rate_per_s"] = rate
        rep = RC.build(cell, args.seed,
                       serving=None if rep is None else rep.serving)
        RC.prime(rep, cell, args.seed)
        if i == 0:
            RC.warm(rep, RC.reachable(rep.rcfg, cell.mix))
        rec = RC.drive(rep, cell, args.seed, args.seconds, None)
        w0, w1 = rec.window
        due = [r for r in rec.reqs.values() if w0 <= r.due < w1]
        done = [r for r in due if r.finish is not None and r.finish <= w1]
        first = [r.first - r.due for r in due if r.first is not None]
        toks = rec.window_tokens
        print(json.dumps({
            "rate": rate, "due": len(due), "finished": len(done),
            "queue_start": queued(rec, w0), "queue_end": queued(rec, w1),
            "ttft_p50_s": float(np.percentile(first, 50)) if first else None,
            "ttft_p90_s": float(np.percentile(first, 90)) if first else None,
            "tokens_per_s": toks / (w1 - w0),
            "occupancy": float(np.mean(rec.occupancy))
            if rec.occupancy else None}), flush=True)
        RC.free(rep)
    return 0


if __name__ == "__main__":
    sys.exit(main())
