#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, many seeds in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 1,2,3 --seconds <s> [--control fp8]

For each seed the replica gets that seed's weights and a fresh runtime
(the programs are built once), runs the cell's own traffic through the
timed path exactly as ``run_cell.py`` does, and the reference compares the
same sample of finished requests. With ``--control`` the lower-precision
reference reads the same positions too, and is put in the program's place
for the run's own checks: ``control_correct`` is the verdict a run would
give it, and has to be false. One JSON line per seed.
Benchmark runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run_cell as RC  # noqa: E402  (sets up sys.path)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None)
    args = ap.parse_args(argv)
    cell = RC.load_cell(RC.ROOT, args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    rep = None
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        rep = RC.build(cell, seed,
                       serving=None if rep is None else rep.serving)
        RC.prime(rep, cell, seed)
        if i == 0:
            RC.warm(rep, RC.reachable(rep.rcfg, cell.mix))
        rec = RC.drive(rep, cell, seed, args.seconds, None)
        e2e = RC.end_to_end(cell, rec, 0.0)
        picked = RC.sample(rec, seed, int(cell.mix["check_requests"]))
        RC.free(rep)
        t = time.perf_counter()
        gap, ctrl = RC.compare(cell, seed, picked, control=args.control)
        chk = RC.checks(cell, rec, gap)
        ctrl_chk = RC.checks(cell, rec, ctrl) if args.control else None
        print(json.dumps({
            "seed": seed, "served_logit_gap": gap, "control": args.control,
            "control_gap": ctrl, "correct": RC.verdict(chk),
            "control_correct": ctrl_chk and RC.verdict(ctrl_chk),
            "judged": len(rec.in_window),
            "unfinished": len(rec.unfinished),
            "checked_requests": len(picked),
            "checked_tokens": sum(len(r.tokens) for r in picked),
            "reference_s": time.perf_counter() - t,
            "metrics": {k: v["value"] for k, v in e2e.items()}}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
