"""A whole run of the harness on the CPU, at the tiny size: everything but
the look for a chip. A sound run is correct; a run whose served tokens are
altered where the program produces them is not; and the fp8 control,
put in the program's place, fails the run's own verdict, which sound runs
pass."""
import numpy as np
import pytest

import chipbench_tiny as T
from benchmarks.chip import run_cell as RC

SEED = 2 ** 31 + 4242


def _run(cell, trace=False, seconds=1.5):
    return RC.run(cell, SEED, seconds, trace, cfg=T.program_cfg(),
                  on_chip=False)


def test_sound_open_loop_run_is_correct():
    out = _run(T.cell("stratified_poisson"))
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 10 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p90_s", "tpot_p90_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["checks"]["served_logit_gap"]["value"] <= T.TINY_LIMIT


def test_traced_backlog_run_reports_its_layers(monkeypatch):
    monkeypatch.setattr(RC, "TRACE_FOR_S", 1.0)
    out = _run(T.cell("backlog"), trace=True, seconds=2.0)
    assert out["correct"] is True, out["checks"]
    m = out["metrics"]
    assert 0 < m["batch_occupancy.batch"]["value"] <= 100
    assert 0 <= m["device_idle_share.chat"]["value"] < 100
    dev = out["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert {n for n, _ in out["breakdown"]["idle_gaps"]} <= {
        "bench.step", "bench.submit", "bench.read_tokens", "bench.wait",
        "host.other"}


def test_a_token_altered_where_it_is_produced_fails(monkeypatch):
    from repro.streaming.runtime import RuntimeKernels
    real = RuntimeKernels.decode_fn

    def broken(self, steps, kvb=0, skip=False):
        fn = real(self, steps, kvb, skip)

        def call(*a, **kw):
            out = list(fn(*a, **kw))
            # every emitted token, on the host: no program is built
            out[4] = (np.asarray(out[4]) + 1) % self.cfg.vocab
            return tuple(out)
        call.lower = fn.lower
        return call

    monkeypatch.setattr(RuntimeKernels, "decode_fn", broken)
    out = _run(T.cell("stratified_poisson"))
    assert out["correct"] is False
    assert out["checks"]["served_logit_gap"]["value"] > T.TINY_LIMIT


def test_fp8_control_fails_where_sound_runs_pass():
    cell = T.cell("backlog")
    rep = RC.build(cell, SEED, cfg=T.program_cfg())
    RC.prime(rep, cell, SEED)
    rec = RC.drive(rep, cell, SEED, 1.0, None)
    picked = RC.sample(rec, SEED, 3)
    RC.free(rep)
    gap, ctrl = RC.compare(cell, SEED, picked, control="fp8")
    assert gap <= T.TINY_LIMIT < ctrl
    assert np.isfinite(ctrl)
    # the run's own verdict: the program passes, the control in its place
    # does not
    assert RC.verdict(RC.checks(cell, rec, gap)) is True
    assert RC.verdict(RC.checks(cell, rec, ctrl)) is False


def test_a_whole_run_with_the_control_in_the_programs_place_is_not_correct():
    out = RC.run(T.cell("stratified_poisson"), SEED, 1.5, False,
                 cfg=T.program_cfg(), on_chip=False, control="fp8")
    assert out["correct"] is False
    assert out["checks"]["served_logit_gap"]["value"] > T.TINY_LIMIT
