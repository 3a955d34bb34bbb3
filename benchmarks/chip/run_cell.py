#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a model
configuration (``configs/<config>.json``) under a traffic mix
(``traffic/<mix>.json``), with its replica sizing and correctness limits in
``cells/<cell>.json``. Per-layer metrics are readers in
``metrics/<metric>.py``. Everything is found by name; nothing here names a
cell.

Stages of a run, each reported on an earlier line of standard output:

1. Set-up (``setup_s``, from process start until the first request is
   due): weights drawn on the device from ``--seed`` in one jitted call,
   an ``ElasticServing`` replica and its ``DecodeRuntime`` with
   ``record_tokens`` on (a server reads its tokens to deliver them, and
   reading them makes every block end on the host), one priming request,
   then every program the cell's traffic can reach built ahead of time
   from JAX's persistent compilation cache.
2. Ramp: the traffic runs until ``ramp_s`` after the first request was
   due; not measured.
3. Window: ``--seconds`` of traffic. Open-loop mixes submit each request
   when it falls due and time it from then; backlog mixes have every
   request due at 0 and count the tokens delivered in the window.
4. Drain (open loop): arrivals go on until every request due in the window
   has finished, or ``drain_cap_s`` passes and it counts as failed.
5. Programs built inside the window must be 0; a run that built one
   exits non-zero.
6. ``correct``: once the program's state is freed, a plain float32
   reference (``reference.py``) runs over a sample of the finished
   requests, drawn from the seed with the longest among them, and every
   served token's reference logit must lie within the cell's limit of the
   reference's best.

``--trace 1`` runs the same cell with the profiler on for the last
``TRACE_FOR_S`` seconds of the window and reports the per-layer metrics and a breakdown instead
of the end-to-end ones. The last line of standard output is one JSON
object; the last lines of standard error repeat the numbers compared,
each beside its limit. Without a TPU, or with fewer chips than the cell
asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import concurrent.futures as cf  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
HERE = pathlib.Path(__file__).resolve().parent
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from benchmarks.chip import traffic as TR  # noqa: E402
from benchmarks.chip import work as WK  # noqa: E402
from benchmarks.chip.work import Dims  # noqa: E402

TRACE_FOR_S = 4.0        # the profiler traces the window's last seconds
COMPILE_THREADS = 8


# ------------------------------------------------------------------ cells

@dataclass
class Cell:
    name: str
    config: dict             # configs/<config>.json
    mix: dict                # traffic/<mix>.json
    sizing: dict             # cells/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell's entry of ``BENCHMARK.json`` and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    here = root / "benchmarks" / "chip"
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    reports = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name] if m["moves"] in reports
                                  else [])]
    return Cell(name=name,
                config=json.loads((root / conf["file"]).read_text()),
                mix=json.loads((here / "traffic" / f"{w['traffic']}.json")
                               .read_text()),
                sizing=json.loads((here / "cells" / f"{name}.json")
                                  .read_text()),
                end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The module of ``metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def pow2_at_least(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def runtime_fields(cell: Cell) -> dict:
    """``RuntimeConfig`` of the cell: the configuration's pool settings, the
    prompt buckets and generation headroom its mix needs, and the cell's
    ``max_batch``."""
    lo, hi = cell.mix["prompt_tokens"]
    out = dict(cell.config["runtime"])
    out.update(min_prompt_bucket=pow2_at_least(lo),
               max_prompt_bucket=pow2_at_least(hi),
               max_new_cap=cell.mix["output_tokens"][1] - 1,
               max_batch=cell.sizing["max_batch"])
    return out


def check_dims(cfg, model: dict) -> None:
    """The program's configuration has to be the one the file states."""
    m = Dims.of(model)
    want = {"d_model": m.d, "n_layers": m.layers, "n_heads": m.heads,
            "n_kv_heads": m.kv_heads, "head_dim": m.head_dim, "d_ff": m.ffn,
            "vocab": m.vocab, "rope_theta": float(model["rope_theta"]),
            "norm_eps": float(model["rms_norm_eps"]),
            "qkv_bias": True, "mlp": "swiglu", "tie_embeddings": False}
    have = {k: getattr(cfg, k) for k in want}
    bad = {k: (have[k], want[k]) for k in want if have[k] != want[k]}
    if bad:
        raise ValueError(f"program config {cfg.name} differs from the "
                         f"cell's file: {bad}")


# ------------------------------------------------------- compile counting

class CompileCounter:
    """Programs XLA builds, through JAX's monitoring events: every build
    (a compile or a load from the persistent cache), the loads among them,
    and the seconds of each."""

    def __init__(self):
        import jax
        self.programs = 0
        self.build_s = 0.0
        self.cache_hits = 0
        self.load_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.build_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.load_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {"programs": self.programs, "cache_hits": self.cache_hits,
                "compiled": self.programs - self.cache_hits,
                "build_s": self.build_s, "load_s": self.load_s}


def say(tag: str, **kv) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


# ------------------------------------------------------------- the replica

@dataclass
class Replica:
    serving: object
    kernels: object
    rt: object
    rcfg: object
    dims: Dims
    tp: int


def make_params(serving, model: dict, seed: int, dtype):
    """Weights in the program's tree, drawn on the devices in one jitted
    call with the program's own parameter shardings (those
    ``ElasticServing`` places its parameters in)."""
    import jax
    from benchmarks.chip import weights as W
    psh = serving._lowered(1)[4]
    m = Dims.of(model)
    fn = jax.jit(lambda k: W.program_params(k, m, dtype), out_shardings=psh)
    params = fn(W.seed_key(seed))
    jax.block_until_ready(params)
    return params


def build(cell: Cell, seed: int, cfg=None, serving=None) -> Replica:
    """An ``ElasticServing`` replica with the seed's weights and a fresh
    ``DecodeRuntime``. ``serving`` reuses a replica's mesh and programs."""
    import jax.numpy as jnp
    from repro.configs.base import get_config
    from repro.core.elastic import ElasticServing
    from repro.streaming.runtime import DecodeRuntime, RuntimeConfig
    tp = int(cell.config["tp"])
    if serving is None:
        cfg = cfg if cfg is not None else get_config(cell.config["arch"])
        check_dims(cfg, cell.config)
        serving = ElasticServing(cfg, tp)
    serving.params = None
    serving.params = make_params(serving, cell.config, seed,
                                 jnp.dtype(serving.cfg.dtype))
    serving.build(1)
    rcfg = RuntimeConfig(**runtime_fields(cell))
    kernels = serving.runtime_kernels(rcfg)
    rt = DecodeRuntime(kernels=kernels, params=serving.params,
                       record_tokens=True)
    return Replica(serving, kernels, rt, rcfg, Dims.of(cell.config), tp)


def reachable(rcfg, mix: dict):
    """Every program the mix can reach: admissions for each batch bucket and
    each prompt bucket its lengths fall in; decode blocks for each step
    bucket and each kv bucket from the shallowest admitted row's first
    step to the deepest row's last."""
    from repro.models import model_api as MA
    lo, hi = mix["prompt_tokens"]
    lbs = sorted({MA.pow2_bucket(n, rcfg.min_prompt_bucket,
                                 rcfg.max_prompt_bucket)
                  for n in (lo, hi)} | {b for b in rcfg.prompt_buckets
                                        if lo <= b <= hi})
    max_new = mix["output_tokens"][1] - 1
    top = next(b for b in rcfg.block_ladder
               if b >= min(max_new, rcfg.decode_block))
    steps = [b for b in rcfg.block_ladder if b <= top]
    kvbs = [b for b in rcfg.kv_ladder if b > lbs[0]
            and b - rcfg.page_size < lbs[-1] + max_new]
    admits = [("admit", bb, lb) for lb in lbs for bb in rcfg.batch_buckets]
    decodes = [("decode", s, kvb) for s in steps for kvb in kvbs]
    return admits + decodes


def prime(rep: Replica, cell: Cell, seed: int) -> None:
    """One request through admission and a decode block, so that the
    runtime's state arrays have the form every later call gives them."""
    from repro.data.pipeline import Request
    hi = cell.mix["prompt_tokens"][1]
    rid = 10 ** 9
    rep.rt.content[rid] = TR.padded(
        TR.prompt_tokens(seed, rid, hi, rep.dims.vocab),
        pow2_at_least(hi))
    rep.rt.submit([Request(rid, 0.0, hi, 2)])
    while rep.rt.pending or any(s.busy for s in rep.rt.slots):
        rep.rt.step()
    rep.rt.token_log.clear()


def warm(rep: Replica, progs) -> None:
    """Build every program ahead of time against the runtime's own state:
    lowered one by one, compiled or loaded from the persistent cache on a
    few threads. A built program fills the jit's own cache, so the window
    dispatches without building anything."""
    rt = rep.rt
    pages = rt._device_pages()
    lowered = []
    for kind, a, b in progs:
        if kind == "admit":
            npg = -(-b // rep.rcfg.page_size)
            lowered.append(rep.kernels.admit_fn(a, b, 0).lower(
                rt.params, np.zeros((a, b), np.int32), rt.cache, rt.tok,
                rt.active, rt.remaining, np.zeros(a, np.int32),
                np.zeros(a, np.int32), pages=pages,
                prompt_pages=np.zeros((a, npg), np.int32)))
        else:
            lowered.append(rep.kernels.decode_fn(a, b).lower(
                rt.params, rt.tok, rt.cache, rt.active, rt.remaining,
                pages=pages))
    with cf.ThreadPoolExecutor(COMPILE_THREADS) as pool:
        for f in [pool.submit(lo.compile) for lo in lowered]:
            f.result()


# ------------------------------------------------------------ the traffic

@dataclass
class Req:
    rid: int
    due: float               # absolute, host clock
    plen: int                # requested prompt tokens
    out: int
    prompt: np.ndarray       # as prefilled, pad to the bucket included
    admit: Optional[float] = None     # start of the step that admitted it
    first: Optional[float] = None     # end of the step of its first token
    finish: Optional[float] = None
    n: int = 0                        # tokens delivered so far
    tokens: Optional[list] = None


@dataclass
class Record:
    """What the drive saw, for the checks and the metric readers."""
    dims: Dims
    tp: int
    max_batch: int
    peak: dict = field(default_factory=dict)
    reqs: Dict[int, Req] = field(default_factory=dict)
    window: tuple = (0.0, 0.0)
    in_window: List[int] = field(default_factory=list)    # rids judged
    unfinished: List[int] = field(default_factory=list)
    window_tokens: int = 0
    ramp_s: float = 0.0
    lateness: List[float] = field(default_factory=list)
    occupancy: List[float] = field(default_factory=list)  # per decode step
    traced_tokens: List[tuple] = field(default_factory=list)  # rid, j0, n
    work: Dict[str, float] = field(default_factory=dict)  # traced steps
    trace: object = None
    steps: int = 0

    def q(self, values, pct):
        return float(np.percentile(np.asarray(values, float), pct)) \
            if len(values) else None


def _annotate(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def book_work(rec: Record) -> None:
    """The work the traced steps' deliveries needed (``work.py``): token
    0 of a request is its prefill of ``plen`` requested tokens; token
    ``j`` >= 1 is a decode step over ``plen + j`` entries of context. The
    pad up to the length bucket joins the program's context but is not
    work the request asked for, so it is never booked."""
    w = dict.fromkeys(("prefill_flops", "prefill_tokens", "attn_flops",
                       "attn_bytes", "decode_flops", "decode_tokens"), 0)
    m, tp = rec.dims, rec.tp
    for rid, j0, n in rec.traced_tokens:
        plen = rec.reqs[rid].plen
        if j0 == 0:
            w["prefill_flops"] += WK.prefill_flops(m, plen, tp)
            w["prefill_tokens"] += plen
        for j in range(max(j0, 1), j0 + n):
            f, b = WK.attn_decode(m, plen + j, tp)
            w["attn_flops"] += f
            w["attn_bytes"] += b
            w["decode_flops"] += WK.decode_token_flops(m, plen + j, tp)
            w["decode_tokens"] += 1
    rec.work = w


def drive(rep: Replica, cell: Cell, seed: int, seconds: float,
          trace_dir: Optional[str]) -> Record:
    """Ramp, window and drain, as the module docstring says."""
    import jax
    from repro.data.pipeline import Request
    mix, rt = cell.mix, rep.rt
    rec = Record(rep.dims, rep.tp, rep.rcfg.max_batch)
    backlog = mix["arrivals"] == "backlog"
    ramp, drain_cap = float(mix["ramp_s"]), float(mix["drain_cap_s"])
    sched = TR.schedule(mix, seed, (ramp, seconds, drain_cap))
    clock = time.perf_counter
    t0 = clock()
    w0 = t0 + ramp if not backlog else None
    w1 = w0 + seconds if not backlog else None
    tracing = trace_dir is not None
    trace_on, trace_done, traced_span = False, False, None
    i, n = 0, len(sched)
    live: Dict[int, Req] = {}
    judged: set = set()
    while True:
        now = clock()
        # the profiler runs over the last seconds of the window, between
        # steps, and is stopped (which writes the trace) once it has closed
        if tracing and not trace_done and w0 is not None:
            if not trace_on and now >= max(w0, w1 - TRACE_FOR_S):
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0     # harness spans, no calls
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                traced_span = jax.profiler.TraceAnnotation("bench.traced")
                traced_span.__enter__()
                trace_on = True
            elif trace_on and now >= w1:
                traced_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                trace_on, trace_done = False, True
        batch = []
        while i < n and t0 + sched[i].due <= now:
            a = sched[i]
            lb = pow2_at_least(max(a.plen, rep.rcfg.min_prompt_bucket))
            r = Req(a.rid, t0 + a.due, a.plen, a.out,
                    TR.padded(TR.prompt_tokens(seed, a.rid, a.plen,
                                               rep.dims.vocab), lb))
            rec.reqs[a.rid] = r
            rt.content[a.rid] = r.prompt
            batch.append(Request(a.rid, a.due, a.plen, a.out - 1))
            if w0 is not None and w0 <= r.due < w1:
                judged.add(a.rid)
                rec.lateness.append(now - r.due)
            i += 1
        if batch:
            with _annotate("bench.submit", trace_on):
                rt.submit(batch)
            live.update((b.rid, rec.reqs[b.rid]) for b in batch)
        if rt.pending or any(s.busy for s in rt.slots):
            ts = clock()
            with _annotate("bench.step", trace_on):
                done = rt.step()
            te = clock()
            rec.steps += 1
            with _annotate("bench.read_tokens", trace_on):
                dec_rows, delivered = 0, 0
                for rid, log in rt.token_log.items():
                    r = live[rid]
                    k = len(log) - r.n
                    if k <= 0:
                        continue
                    if r.n == 0:
                        r.first, r.admit = te, ts
                    if trace_on:
                        rec.traced_tokens.append((rid, r.n, k))
                    dec_rows += k > (r.n == 0)
                    delivered += k
                    r.n = len(log)
                for f in done:
                    r = live.pop(f.req.rid)
                    r.finish = te
                    r.tokens = rt.token_log.pop(f.req.rid)
            if w0 is not None and w0 < te <= w1:
                rec.window_tokens += delivered
                if dec_rows:
                    rec.occupancy.append(dec_rows / rep.rcfg.max_batch)
            if backlog and w0 is None and te >= t0 + ramp:
                w0 = te
                w1 = w0 + seconds
            elif backlog and w0 is not None and te >= w1:
                w1 = te
                break
        elif i < n:
            wait = t0 + sched[i].due - clock()
            if wait > 0:
                with _annotate("bench.wait", trace_on):
                    time.sleep(wait)
        else:
            break
        if not backlog and clock() >= w1:
            # a request due in the window but not yet submitted is open too
            open_ = [rid for rid in judged if rec.reqs[rid].finish is None]
            unsent = i < n and t0 + sched[i].due < w1
            if not (open_ or unsent) or clock() >= w1 + drain_cap:
                rec.unfinished = sorted(open_)
                break
    if trace_on:
        traced_span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    rec.window = (w0, w1)
    rec.ramp_s = w0 - t0
    if backlog:
        judged = {rid for rid, r in rec.reqs.items()
                  if r.finish is not None and w0 < r.finish <= w1}
    rec.in_window = sorted(judged)
    return rec


# -------------------------------------------------------------- the check

def sample(rec: Record, seed: int, k: int) -> List[Req]:
    """The longest finished request judged in the window and ``k - 1`` more
    drawn from the seed."""
    done = [rec.reqs[rid] for rid in rec.in_window
            if rec.reqs[rid].tokens is not None]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 13]))
    pick = rng.permutation(len(rest))[:max(k - 1, 0)]
    return [longest] + [rest[j] for j in sorted(pick)]


def compare(cell: Cell, seed: int, picked: List[Req], control=None):
    """(widest served-token gap, the control's widest gap or None)."""
    from benchmarks.chip import reference as REF
    if not picked:
        raise RuntimeError("no finished request in the window to check")
    lb_max = pow2_at_least(cell.mix["prompt_tokens"][1])
    out_hi = cell.mix["output_tokens"][1]
    return REF.served_gaps(
        seed, cell.config, [(r.prompt, np.asarray(r.tokens, np.int32))
                            for r in picked],
        rows=int(cell.sizing["reference_rows"]),
        seq_len=lb_max + out_hi - 1, out_len=out_hi, control=control)


def free(rep: Replica) -> None:
    """Drop the program's state so the reference has the chip."""
    rep.rt.cache = rep.rt.tok = rep.rt.active = rep.rt.remaining = None
    rep.rt.params = None
    rep.rt._pages_dev = None
    rep.serving.params = None
    gc.collect()


def checks(cell: Cell, rec: Record, gap: float) -> dict:
    """Each number compared, with its limit (a value above it fails)."""
    wrong = [rid for rid in rec.in_window
             if rec.reqs[rid].tokens is not None
             and len(rec.reqs[rid].tokens) != rec.reqs[rid].out]
    return {"served_logit_gap": {"value": gap,
                                 "limit": cell.sizing["limits"]
                                 ["served_logit_gap"]},
            "wrong_length": {"value": len(wrong), "limit": 0},
            "unfinished": {"value": len(rec.unfinished), "limit": 0}}


def verdict(chk: dict) -> bool:
    """``correct``: every number compared within its limit."""
    return all(c["value"] <= c["limit"] for c in chk.values())


# ------------------------------------------------------------ the metrics

def end_to_end(cell: Cell, rec: Record, setup_s: float) -> dict:
    out = {"setup_s": setup_s}
    judged = [rec.reqs[rid] for rid in rec.in_window]
    w0, w1 = rec.window
    cap = w1 + float(cell.mix["drain_cap_s"])
    ttft = [(r.first if r.first is not None else cap) - r.due
            for r in judged]
    tpot = [((r.finish if r.finish is not None else cap) - r.first)
            / (len(r.tokens) - 1) * 1e3
            for r in judged if r.first is not None and r.tokens
            and len(r.tokens) > 1]
    avail = {"ttft_p90_s": lambda: rec.q(ttft, 90),
             "tpot_p90_ms": lambda: rec.q(tpot, 90),
             "output_tokens_per_s": lambda: rec.window_tokens / (w1 - w0)}
    for m in cell.end_to_end:
        if m["name"] != "setup_s":
            out[m["name"]] = avail[m["name"]]()
    return {m["name"]: {"value": out[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell: Cell, rec: Record) -> dict:
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"]).read(rec.trace, rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def memory_peak(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


# ------------------------------------------------------------------- main

def run(cell: Cell, seed: int, seconds: float, trace: bool, *, cfg=None,
        on_chip: bool = True, t_start: float = T_START,
        control: Optional[str] = None) -> dict:
    """One run; returns the result object. ``on_chip=False`` (the tests)
    leaves the persistent compilation cache off, skips the check that the
    kernels resolved to Pallas and reads no table of peaks. ``control``
    (``"fp8"``) puts the lower-precision reference in the program's place
    for the checks, which it has to fail."""
    import jax
    from repro.kernels import ops as OPS
    from repro.launch.compile_cache import enable_compile_cache

    if on_chip:
        say("cache", dir=enable_compile_cache())
    counter = CompileCounter()
    t = time.perf_counter()
    rep = build(cell, seed, cfg=cfg)
    if on_chip and OPS.resolved_mode() != "pallas":
        raise RuntimeError(f"kernels resolved to {OPS.resolved_mode()!r}, "
                           f"not 'pallas'")
    t_build = time.perf_counter() - t
    devices = list(rep.serving.mesh.devices.flat)
    say("replica", arch=rep.serving.cfg.name, tp=rep.tp,
        kernel_mode=OPS.resolved_mode(), build_s=f"{t_build:.3f}",
        weight_bytes=sum(x.nbytes for x in jax.tree.leaves(rep.rt.params)),
        **{k: v for k, v in runtime_fields(cell).items()})
    t = time.perf_counter()
    prime(rep, cell, seed)
    t_prime = time.perf_counter() - t
    progs = reachable(rep.rcfg, cell.mix)
    before = counter.snapshot()
    t = time.perf_counter()
    warm(rep, progs)
    t_warm = time.perf_counter() - t
    after = counter.snapshot()
    n_adm = sum(p[0] == "admit" for p in progs)
    say("programs", reachable=len(progs), admit=n_adm,
        decode=len(progs) - n_adm,
        max_traces_bound=rep.kernels.max_traces,
        traces=json.dumps(rep.kernels.trace_counts).replace(" ", ""))
    say("setup", prime_s=f"{t_prime:.3f}", warm_s=f"{t_warm:.3f}",
        warm_built=after["programs"] - before["programs"],
        warm_from_cache=after["cache_hits"] - before["cache_hits"],
        warm_compiled=after["compiled"] - before["compiled"],
        warm_load_s=f"{after['load_s'] - before['load_s']:.3f}",
        warm_build_s=f"{after['build_s'] - before['build_s']:.3f}",
        **{f"total_{k}": (f"{v:.3f}" if isinstance(v, float) else v)
           for k, v in after.items()})

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        setup_s = time.perf_counter() - t_start
        before = counter.snapshot()
        rec = drive(rep, cell, seed, seconds, trace_dir)
        in_run = counter.programs - before["programs"]
        peak = memory_peak(devices)
        late = rec.lateness or [0.0]
        say("run", setup_s=f"{setup_s:.3f}", ramp_s=f"{rec.ramp_s:.3f}",
            window_s=f"{rec.window[1] - rec.window[0]:.3f}",
            steps=rec.steps, judged=len(rec.in_window),
            submitted=len(rec.reqs), unfinished=len(rec.unfinished),
            window_tokens=rec.window_tokens,
            lateness_p50_s=f"{np.percentile(late, 50):.4f}",
            lateness_max_s=f"{max(late):.4f}",
            built_in_run=in_run, peak_bytes_in_use=peak)
        if in_run:
            raise RuntimeError(f"{in_run} programs were built after set-up;"
                               f" the warm-up missed a shape")
        kind = devices[0].device_kind
        rec.peak = WK.peaks(kind) if on_chip else {
            "bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}
        if trace:
            from benchmarks.chip import trace as TRC
            rec.trace = TRC.load(trace_dir)
            book_work(rec)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = per_layer(cell, rec) if trace else \
        end_to_end(cell, rec, setup_s)
    picked = sample(rec, seed, int(cell.mix["check_requests"]))
    free(rep)
    t = time.perf_counter()
    gap, ctrl = compare(cell, seed, picked, control=control)
    say("check", requests=len(picked),
        served_tokens=sum(len(r.tokens) for r in picked),
        reference_s=f"{time.perf_counter() - t:.3f}")
    chk = checks(cell, rec, gap if control is None else ctrl)
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": verdict(chk),
           "attempted": len(rec.in_window), "failed": len(rec.unfinished),
           "metrics": metrics, "device": dev}
    if trace:
        dev["busy_s"] = rec.trace.mean_busy_s()
        dev["window_s"] = rec.trace.window_s
        out["breakdown"] = {"device_ops": rec.trace.top_ops(),
                            "idle_gaps": rec.trace.idle_by_host()}
    out["checks"] = chk
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    import jax
    devices = jax.devices()
    chips = int(cell.config["chips"])
    say("device", platform=devices[0].platform,
        kind=devices[0].device_kind.replace(" ", "_"), count=len(devices))
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run_cell: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    out = run(cell, args.seed, args.seconds, bool(args.trace))
    for k, c in out["checks"].items():
        print(f"check {k}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
