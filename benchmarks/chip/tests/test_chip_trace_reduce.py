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
    # an operation that takes the kernel's result names it as an operand
    t.ops.append(TRC.Op(0, "%fusion.4 = bf16[8] fusion(%paged_decode_"
                        "attention.3)", "jit_block", 2.5, 0.5))
    assert t.op_s(0, "paged_decode_attention") == pytest.approx(1.0)
    assert t.program_s(0, "jit_block") == pytest.approx(1.5)
    assert t.program_s(0, "jit_admit") == pytest.approx(1.5)


def test_collective_time_is_a_union_on_the_chip():
    t = _synthetic()
    t.ops += [TRC.Op(0, "%all-reduce.1 = f32[8] all-reduce(%x)", "jit_block",
                     1.2, 0.2),
              TRC.Op(0, "%all-reduce-start.2 = f32[8] all-reduce-start(%y)",
                     "jit_block", 1.3, 0.2),
              # a consumer of the all-reduce, not a collective
              TRC.Op(0, "%fusion.7 = f32[8] fusion(%all-reduce.1)",
                     "jit_block", 1.6, 0.4)]
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


def test_collective_share_over_four_device_planes(tmp_path, monkeypatch):
    """A v5e-4 trace as the profiler lays it out: one ``/device:TPU:<n>``
    plane a chip, each with its ``XLA Modules`` and ``XLA Ops`` lines, and
    the harness's spans on a host plane. The tests run on the CPU, which
    leaves the exchange between chips out; the planes are written here."""
    from types import SimpleNamespace as NS
    from benchmarks.chip import run_cell as RC

    def ev(name, start_s, dur_s):
        return NS(name=name, start_ns=int(start_s * 1e9),
                  duration_ns=int(dur_s * 1e9), stats=[])

    def line(name, events):
        return NS(name=name, events=events)

    planes = []
    for n in range(4):
        ar = 0.1 * (n + 1)          # chip n: all-reduce time 0.1 (n + 1) s
        ops = [ev("%fusion.1 = bf16[8] fusion(%p)", 1.0, 1.0),
               ev("%all-reduce.7 = bf16[8] all-reduce(%f)", 2.0, ar),
               ev("%all-gather.2 = bf16[8] all-gather(%g)", 12.0, 1.0)]
        if n == 0:                  # overlapping async halves count once
            ops.append(ev("%all-reduce-start.8 = bf16[8] "
                          "all-reduce-start(%h)", 2.05, 0.05))
        planes.append(NS(name=f"/device:TPU:{n}", lines=[
            line("XLA Modules", [ev("jit_block(3)", 1.0, 2.0)]),
            line("XLA Ops", ops)]))
    planes.append(NS(name="/host:CPU", lines=[
        line("python", [ev("bench.traced", 0.0, 10.0)])]))
    (tmp_path / "x.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(jax.profiler, "ProfileData", NS(
        from_file=lambda path: NS(planes=planes)))
    t = TRC.load(str(tmp_path))
    assert t.chips == [0, 1, 2, 3]
    # the all-gather lies past the window's end
    assert [t.collective_s(c) for c in t.chips] == pytest.approx(
        [0.1, 0.2, 0.3, 0.4])
    read = RC.metric_reader("collective_share.tp4").read
    assert read(t, None) == pytest.approx(100.0 * 0.25 / 10.0)
    assert read(None, None) is None
    quiet = TRC.Trace([TRC.Op(c, "fusion.1", "jit_block", 1.0, 1.0)
                       for c in range(4)], [], (0.0, 10.0), [0, 1, 2, 3])
    assert read(quiet, None) is None
