"""The runtime's own spans read beside a run: idle charged to the
innermost span, the ring and the profiler on one clock, and a whole
traced run on the CPU at the tiny size."""
import time

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as T
from benchmarks.chip import run_cell as RC
from benchmarks.chip import runtime_spans as RS
from benchmarks.chip import runtime_trace as RT
from benchmarks.chip import trace as TRC
from repro.core.tracing import Tracer

SEED = 2 ** 31 + 4343


def _busy_trace(spans):
    ops = [TRC.Op(0, "fusion.1", "jit_block", 1.0, 1.5),
           TRC.Op(0, "fusion.2", "jit_admit", 4.0, 1.0),
           TRC.Op(0, "fusion.9", "jit_admit", 9.5, 2.0)]
    return TRC.Trace(ops, spans, (0.0, 10.0), [0])


def test_idle_is_charged_once_to_the_innermost_span():
    spans = [("bench.traced", 0.0, 10.0), ("bench.step", 0.0, 3.0),
             ("runtime.decode", 0.0, 2.8),
             ("runtime.decode_read", 2.6, 2.8), ("bench.wait", 3.0, 4.0),
             ("bench.read_tokens", 5.0, 6.0), ("runtime.harvest", 5.5, 5.7)]
    got = dict(RS.idle_by_innermost(_busy_trace(spans), spans))
    # idle: [0,1) [2.5,4) [5,9.5); busy 3 of 10
    assert got == pytest.approx({
        "runtime.decode": 1.1, "runtime.decode_read": 0.2,
        "bench.step": 0.2, "bench.wait": 1.0, "bench.read_tokens": 0.8,
        "runtime.harvest": 0.2, "host.other": 3.5})
    assert sum(got.values()) == pytest.approx(10.0 - 3.0)


def test_with_harness_spans_alone_the_charges_are_the_harness_reading():
    spans = [("bench.traced", 0.0, 10.0), ("bench.step", 0.0, 3.0),
             ("bench.wait", 3.0, 4.0), ("bench.read_tokens", 5.0, 6.0)]
    t = _busy_trace(spans)
    assert dict(RS.idle_by_innermost(t, spans)) == pytest.approx(
        dict(t.idle_by_host()))


def test_the_ring_and_the_profiler_share_one_clock(tmp_path):
    @jax.jit
    def block(x):
        return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((128, 128))
    block(x).block_until_ready()
    tr = Tracer()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(4):
            with tr.region("decode", steps=4):
                with tr.region("decode_read"):
                    block(x).block_until_ready()
                time.sleep(0.005)
            with tr.region("harvest"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    host = sorted(RS.host_spans(str(tmp_path)), key=lambda s: s[1])
    ring = sorted(tr.spans, key=lambda s: s.wall)
    assert [s[0] for s in host] == ["runtime." + s.name for s in ring]
    offsets = [h[1] - s.wall for h, s in zip(host, ring)]
    assert max(offsets) - min(offsets) < 1e-3
    for h, s in zip(host, ring):
        assert h[2] - h[1] == pytest.approx(s.dur, abs=1e-3)


def test_readers_say_nothing_when_the_ring_dropped_spans():
    tr = Tracer(cap=2)
    for _ in range(3):
        tr.span("decode", 0.0, rows=5, steps=4, live_row_steps=4)
    rec = RC.Record(None, 1, 4, window=(0.0, 1e12))
    for read in (RS.admit_wait_p90, RS.first_token_hold_p90,
                 RS.dead_row_steps, RS.order_faults):
        assert read(tr, rec) is None
        assert read(None, rec) is None


def test_a_traced_open_loop_run_reads_the_runtime_spans(monkeypatch):
    monkeypatch.setattr(RC, "TRACE_FOR_S", 1.0)
    seeded, profiled = list(RT.measure(T.cell("stratified_poisson"), [SEED],
                                       1.5, SEED + 1, cfg=T.program_cfg()))
    for tag in ("on", "off"):
        assert seeded[tag]["judged"] >= 10
        assert seeded[tag]["unfinished"] == 0
        assert seeded[tag]["ttft_p90_s"] > 0
    for ring in (seeded["ring"], profiled["ring"]):
        assert ring["dropped"] == 0 and ring["order_faults"] == 0
        assert ring["admit_wait_p90_s"] >= 0
        assert ring["first_token_hold_p90_s"] >= 0
        assert 0 <= ring["decode_dead_row_steps"] < 100
    idle = dict(profiled["idle_gaps"])
    assert any(k.startswith("runtime.") for k in idle)
    assert profiled["runtime_spans"] > 0
    assert sum(idle.values()) == pytest.approx(
        profiled["window_s"] - profiled["busy_s"], rel=1e-6)
    assert sum(v for _, v in profiled["idle_gaps_bench_only"]) == \
        pytest.approx(sum(idle.values()), rel=1e-6)


def test_a_backlog_run_books_its_dead_row_steps():
    (seeded,) = RT.measure(T.cell("backlog"), [SEED], 1.0,
                           cfg=T.program_cfg())
    assert seeded["on"]["output_tokens_per_s"] > 0
    # the overflow row alone leaves one row in max_batch + 1 dead
    assert 100 / 5 <= seeded["ring"]["decode_dead_row_steps"] < 100
