"""The reduction from a profiler trace to device numbers."""
import time

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny  # noqa: F401  (puts the checkout on sys.path)
from benchmarks.chip import trace as TRC


def _synthetic():
    ops = [TRC.Op(0, "%while.5 = (s32[]) while(%t)", "jit_block", 1.0, 1.5),
           TRC.Op(0, "fusion.1", "jit_block", 1.0, 1.0),
           TRC.Op(0, "paged_decode_attention.3", "jit_block", 1.5, 1.0),
           TRC.Op(0, "fusion.2", "jit_admit", 4.0, 1.0),
           TRC.Op(0, "fusion.9", "jit_admit", 9.5, 2.0)]     # past the end
    spans = [("bench.traced", 0.0, 10.0), ("bench.step", 0.0, 3.0),
             ("bench.wait", 3.0, 4.0), ("bench.read_tokens", 5.0, 6.0)]
    modules = [TRC.Op(0, "jit_block(17)", "jit_block(17)", 1.0, 1.5),
               TRC.Op(0, "jit_admit(5)", "jit_admit(5)", 4.0, 1.0),
               TRC.Op(0, "jit_admit(5)", "jit_admit(5)", 9.5, 2.0)]
    return TRC.Trace(ops, spans, (0.0, 10.0), [0], modules)


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    t = _synthetic()
    assert t.intervals(0) == [(1.0, 2.5), (4.0, 5.0), (9.5, 10.0)]
    assert t.busy_s(0) == pytest.approx(3.0)
    assert t.window_s == 10.0


def test_kernel_and_program_time_by_name():
    t = _synthetic()
    top = dict(t.top_ops())
    assert top["jit_block/paged_decode_attention.3"] == pytest.approx(1.0)
    assert TRC.op_name("%fusion.12 = bf16[2]{0} fusion(%p)") == "fusion.12"
    assert t.op_s(0, "paged_decode_attention") == pytest.approx(1.0)
    assert t.program_s(0, "jit_block") == pytest.approx(1.5)
    assert t.program_s(0, "jit_admit") == pytest.approx(1.5)


def test_collective_time_is_a_union_on_the_chip():
    t = _synthetic()
    t.ops += [TRC.Op(0, "%all-reduce.1 = f32[8] all-reduce(%x)", "jit_block",
                     1.2, 0.2),
              TRC.Op(0, "%all-reduce-start.2 = f32[8] all-reduce-start(%y)",
                     "jit_block", 1.3, 0.2)]
    assert t.collective_s(0) == pytest.approx(0.3)
    assert t.busy_s(0) == pytest.approx(3.0)


def test_idle_gaps_are_charged_to_the_host_span_over_them():
    got = dict(_synthetic().idle_by_host())
    # idle: [0,1) step, [2.5,3) step, [3,4) wait, [5,6) read, [6,9.5) other
    assert got == pytest.approx({"bench.step": 1.5, "bench.wait": 1.0,
                                 "bench.read_tokens": 1.0,
                                 "host.other": 3.5})
    assert sum(got.values()) == pytest.approx(10.0 - 3.0)


def test_a_trace_recorded_on_the_cpu(tmp_path):
    @jax.jit
    def block(x):
        return jnp.tanh(x @ x.T).sum()

    x = jnp.ones((256, 256))
    block(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.traced"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                block(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(0.02)
    jax.profiler.stop_trace()
    t = TRC.load(str(tmp_path))
    assert t.chips == [0]
    assert 0.06 < t.window_s < 5.0
    busy = t.busy_s(0)
    assert 0.0 < busy < t.window_s
    dot = t.op_s(0, "dot")
    assert 0.0 < dot <= busy
    assert t.program_s(0, "jit_block") == pytest.approx(
        sum(o.dur for o in t.ops if o.module.startswith("jit_block")),
        rel=1e-6)
    idle = dict(t.idle_by_host())
    assert idle.get("bench.wait", 0.0) >= 0.05
    assert sum(idle.values()) == pytest.approx(t.window_s - busy, rel=1e-6)
