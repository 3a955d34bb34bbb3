#!/usr/bin/env python3
"""Smoke test of the served path on a TPU, at the published widths.

    python chip_smoke.py             one chip: qwen2-7b-1chip (16 of 28
                                     layers, every width as published), tp 1
    python chip_smoke.py --chips 4   four chips: the uncut 28-layer
                                     qwen2-7b, tensor-parallel over 4 (tp 4)

Both run the same two phases in one process, on the mesh that serving
builds:

1. Serve. ``repro.launch.serve.main`` brings up the control plane and a
   replica (``--paged --kernel-mode auto --vary-shapes``: prompts of
   8-48 tokens, 2-16 new), then the script drains the engine. It asserts
   that auto resolved to the Pallas kernels and that every submitted
   request was served exactly once.
2. Logits. For a few prompts, the replica's own jitted admission and
   fused-decode functions (the programs that served phase 1) generate
   greedy tokens through the paged cache; the fused decode returns its
   last step's logits. Those are compared with the uncached ``prefill``
   forward over prompt plus the tokens the last step had consumed. The
   same runtime code is run again in jnp mode (fresh traces) and checked
   the same way, and its logits are compared with the Pallas ones on the
   rows where both modes generated the same tokens.

Tolerance (``LOGIT_TOL``): weights, activations, the residual stream and
the KV cache are bfloat16 (8 significant bits, a relative step of
2^-8 = 0.0039). Cached decode and the uncached forward round at different
points (one token's projections against a whole sequence's, a Pallas
float32 softmax against a blocked jnp one), so each layer may differ by a
few bf16 steps of the residual. The check bounds the largest logit error
by ``LOGIT_TOL`` times the largest logit magnitude of the reference.

Two controls show that the check can fail: the same served programs run
with the page table shifted by one row (each request attends another's
KV), and with KV head 0 of every layer zeroed after admission. Each must
read above ``LOGIT_TOL`` against its own uncached forward.

Lines before the last report the device, the model, the programs XLA
built (how many came from the persistent cache, how many were compiled,
and the seconds both took), set-up seconds, weight bytes, peak
device bytes, requests served and wall seconds. The last line is
``{"ok": true, "device": {...}}``. The script exits non-zero, and prints
no ok line, when JAX finds no TPU, when the repository is not beside it,
or when any phase fails.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

LOGIT_TOL = 0.05        # largest |logit error| / largest |reference logit|
N_PROMPTS = 8
PROMPT_LEN = 32
N_NEW = 8
SERVE_TICKS = 2
LAM_SCALE = 0.006       # arrival rate scale: 17 requests over the two ticks


class CompileCounter:
    """Counts the programs XLA builds, and how many of them came from the
    persistent compilation cache, through JAX's monitoring events (the
    backend compile duration covers a cache load as well as a compile)."""

    def __init__(self):
        import jax
        self.programs = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def serve_phase(arch: str, chips: int):
    """Serve the --vary-shapes mix through repro.launch.serve, then drain.
    Returns the engine."""
    from repro.kernels import ops as OPS
    from repro.launch import serve
    engine = serve.main([
        "--arch", arch, "--devices", str(chips), "--tp", str(chips),
        "--nodes", "1", "--paged", "--kernel-mode", "auto", "--vary-shapes",
        "--ticks", str(SERVE_TICKS), "--lam-scale", str(LAM_SCALE)])
    if OPS.resolved_mode() != "pallas":
        raise RuntimeError(f"kernel mode auto resolved to "
                           f"{OPS.resolved_mode()!r}, not 'pallas'")
    dt, now = 10.0, SERVE_TICKS * 10.0
    for _ in range(100):                      # drain: no new arrivals
        busy = any(rt.pending or any(s.busy for s in rt.slots)
                   for rt in engine.runtimes.values())
        if not engine.queue and not busy:
            break
        engine.reconcile(now)
        engine.tick(now, dt, 0.0)
        now += dt
    else:
        raise RuntimeError(f"engine did not drain: queue={len(engine.queue)}")
    submitted = engine.source.rid
    rids = [rid for rid, _ in engine.completed]
    if len(rids) != len(set(rids)):
        raise RuntimeError("a request was served more than once")
    if set(rids) != set(range(1, submitted + 1)):
        raise RuntimeError(f"served {sorted(rids)}, submitted 1..{submitted}")
    if submitted < 8:
        raise RuntimeError(f"only {submitted} requests arrived; need >= 8")
    return engine


def _relerr(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def logits_phase(serving, rcfg, seed: int = 0) -> dict:
    """Greedy generation through the replica's own jitted admission and
    fused decode, in Pallas mode (the served programs) and in jnp mode;
    the last decode step's logits against the uncached prefill forward,
    plus the two controls. Returns the errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops as OPS
    from repro.models import model_api as MA
    from repro.streaming.runtime import RuntimeKernels

    cfg, ctx, params = serving.cfg, serving.ctx, serving.params
    mod = MA.get_module(cfg)
    ps, rows = rcfg.page_size, rcfg.max_batch + 1
    B, lb, n = min(N_PROMPTS, rcfg.max_batch), PROMPT_LEN, N_NEW
    kvb = -(-(lb + n) // ps) * ps             # covers positions 0..lb+n-1
    npg = kvb // ps
    table = np.zeros((rows, rcfg.pages_per_slot), np.int32)
    table[:B, :npg] = 1 + np.arange(B * npg).reshape(B, npg)
    shifted = table.copy()
    shifted[:B] = np.roll(table[:B], 1, axis=0)
    prompts = np.random.default_rng(seed).integers(
        0, cfg.vocab, (B, lb)).astype(np.int32)

    def generate(kernels, decode_table=table, after_admit=None):
        """Admit the prompts, then one fused block of n greedy steps.
        Returns the tokens the last step had consumed (g_0..g_{n-1}) and
        that step's logits."""
        put = kernels.put
        cache = put(MA.init_paged_cache(cfg, rows, 1 + B * npg, ps),
                    MA.paged_cache_axes(cfg))
        tok = put(jnp.zeros((rows, 1), jnp.int32))
        active = put(jnp.zeros((rows,), bool))
        remaining = put(jnp.zeros((rows,), jnp.int32))
        cache, tok, active, remaining, first = kernels.admit_fn(B, lb, 0)(
            params, prompts, cache, tok, active, remaining,
            np.arange(B, dtype=np.int32), np.full(B, n, np.int32),
            pages=put(jnp.asarray(table)),
            prompt_pages=table[:B, :-(-lb // ps)])
        if after_admit is not None:
            cache = after_admit(cache)
        _, _, _, _, toks, last = kernels.decode_fn(n, kvb)(
            params, tok, cache, active, remaining,
            pages=put(jnp.asarray(decode_table)))
        gen = np.concatenate([np.asarray(first)[:, None],
                              np.asarray(toks)[:n - 1, :B].T], axis=1)
        return gen, np.asarray(last)[:B]

    forward = jax.jit(lambda p, t: mod.prefill(p, t, cfg, ctx)[0])

    def vs_forward(gen, last):
        ref = forward(params, np.concatenate([prompts, gen], axis=1))
        return _relerr(last, ref)

    def zero_head(cache):
        return {part: c if part == "pos" else
                {nm: kv.at[:, :, 0].set(0) for nm, kv in c.items()}
                for part, c in cache.items()}

    served = serving.runtime_kernels(rcfg)
    g_p, l_p = generate(served)
    out = {"pallas_vs_ref": vs_forward(g_p, l_p)}
    OPS.set_kernel_mode("jnp")
    try:      # fresh kernels: the mode is read only while tracing
        g_j, l_j = generate(RuntimeKernels(cfg, rcfg, ctx))
    finally:
        OPS.set_kernel_mode(None)
    out["jnp_vs_ref"] = vs_forward(g_j, l_j)
    same = (g_p == g_j).all(axis=1)
    out["rows_same_tokens"] = int(same.sum())
    out["pallas_vs_jnp"] = (_relerr(l_p[same], l_j[same]) if same.any()
                            else float("nan"))
    out["control_shifted_pages"] = vs_forward(
        *generate(served, decode_table=shifted))
    out["control_zeroed_head"] = vs_forward(
        *generate(served, after_admit=zero_head))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: qwen2-7b-1chip at tp 1; 4: qwen2-7b at tp 4")
    args = ap.parse_args(argv)
    t0 = time.perf_counter()
    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: no TPU found; there is no CPU fallback",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} found",
              file=sys.stderr)
        return 2
    print(f"[cache] persistent compilation cache: {enable_compile_cache()}")
    counter = CompileCounter()
    arch = "qwen2-7b-1chip" if args.chips == 1 else "qwen2-7b"

    engine = serve_phase(arch, args.chips)
    serve_s = time.perf_counter() - t0
    serving = engine.serving
    print(f"[serve] served={len(engine.completed)} "
          f"submitted={engine.source.rid} exactly_once=True "
          f"tokens={engine.total_tokens} kernel_mode=pallas "
          f"wall_s={serve_s:.3f}")
    weight_bytes = sum(x.nbytes for x in jax.tree.leaves(serving.params))
    errs = logits_phase(serving, engine.runtime_cfg)
    print(f"[logits] tol={LOGIT_TOL} " + " ".join(
        f"{k}={v:.6f}" if isinstance(v, float) else f"{k}={v}"
        for k, v in errs.items()))
    bad = [k for k in ("pallas_vs_ref", "jnp_vs_ref", "pallas_vs_jnp")
           if not errs[k] <= LOGIT_TOL]
    bad += [k for k in ("control_shifted_pages", "control_zeroed_head")
            if not errs[k] > LOGIT_TOL]
    if bad:
        raise RuntimeError(f"logits check failed ({', '.join(bad)}; tol "
                           f"{LOGIT_TOL}, controls must exceed it): {errs}")
    peak = max(d.memory_stats().get("peak_bytes_in_use", 0)
               for d in serving.mesh.devices.flat)
    print(f"[setup] programs={counter.programs} "
          f"cache_hits={counter.cache_hits} "
          f"compiled={counter.programs - counter.cache_hits} "
          f"compile_s={counter.compile_s:.3f} "
          f"weight_bytes={weight_bytes} peak_bytes_in_use={peak} "
          f"wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
