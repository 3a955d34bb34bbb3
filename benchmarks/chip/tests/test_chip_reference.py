"""The float32 reference against the program's own uncached forward, the
seeded weights drawn layer by layer against the stacked draw, and the
``tp_local`` control of a tensor-parallel cell: the exchange between chips
left out."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as T
from benchmarks.chip import reference as REF
from benchmarks.chip import run_cell as RC
from benchmarks.chip import traffic as TR
from benchmarks.chip import weights as W
from benchmarks.chip.work import Dims

SEED = 2 ** 31 + 31


def test_one_layer_draws_as_the_stacked_draw():
    m = Dims.of(T.MODEL)
    key = W.seed_key(SEED)
    stacked = W.program_params(key, m)["dense_layers"]
    for l in range(m.layers):
        one = W.layer(key, l, m)
        for nm, a in one.items():
            assert np.array_equal(np.asarray(a), np.asarray(stacked[nm][l]))


def test_reference_matches_program_prefill_with_the_pad():
    from repro.models import model_api as MA
    cfg = T.program_cfg("float32")
    m = Dims.of(T.MODEL)
    params = W.program_params(W.seed_key(SEED), m, jnp.float32)
    # a 20-token prompt padded to its 32 bucket, as the runtime prefills it
    toks = TR.padded(TR.prompt_tokens(SEED, 1, 20, m.vocab), 32)
    mod = MA.get_module(cfg)
    with jax.default_matmul_precision("highest"):
        got, _ = mod.prefill(params, jnp.asarray(toks[None]), cfg)
    # the reference draws bf16-rounded weights; round the program's alike
    params16 = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                            .astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        got16, _ = mod.prefill(params16, jnp.asarray(toks[None]), cfg)
    ref = REF.logits(SEED, T.MODEL, toks[None], np.array([[31]]))[:, 0]
    scale = float(np.abs(ref).max())
    # float32 on both sides, summed in another order: a few ulps per layer
    err16 = np.abs(np.asarray(got16) - ref).max()
    assert err16 <= 1e-3 * scale
    # the bf16 rounding of the weights is what separates the reference from
    # the unrounded float32 program, by far more than that
    assert np.abs(np.asarray(got) - ref).max() > 10 * err16


def _served_tp4(seed):
    """The tiny tp 4 model's program, run unsharded on this process's one
    device (the same sums, in another order), and a sample of what it
    served."""
    cell = T.cell("backlog", model=dict(T.MODEL_TP4, tp=1, chips=1))
    rep = RC.build(cell, seed, cfg=T.program_cfg(model=T.MODEL_TP4))
    RC.prime(rep, cell, seed)
    rec = RC.drive(rep, cell, seed, 1.0, None)
    picked = RC.sample(rec, seed, 3)
    RC.free(rep)
    cell.config.update(tp=4, chips=4)
    return cell, rec, picked


def test_tp_local_control_fails_where_sound_runs_pass():
    cell, rec, picked = _served_tp4(SEED)
    gap, f8 = RC.compare(cell, SEED, picked, control="fp8")
    _, local = RC.compare(cell, SEED, picked, control="tp_local")
    assert gap <= T.TINY_LIMIT
    # three quarters of every layer's two sums left out: far past fp8
    assert local > 5 * T.TINY_LIMIT and local > f8 > T.TINY_LIMIT
    assert RC.verdict(RC.checks(cell, rec, gap)) is True
    assert RC.verdict(RC.checks(cell, rec, local)) is False


def test_controls_leave_the_sound_reading_unchanged():
    cell, _, picked = _served_tp4(SEED + 1)
    alone, none = RC.compare(cell, SEED + 1, picked)
    assert none is None
    for control in ("fp8", "tp_local"):
        gap, _ = RC.compare(cell, SEED + 1, picked, control=control)
        assert gap == alone
    # both controls in one pass read as each does alone
    reqs = [(r.prompt, np.asarray(r.tokens, np.int32)) for r in picked]
    hi = cell.mix["output_tokens"][1]
    kw = dict(rows=cell.sizing["reference_rows"], out_len=hi,
              seq_len=RC.pow2_at_least(cell.mix["prompt_tokens"][1]) + hi - 1)
    both = REF.served_gaps(SEED + 1, cell.config, reqs,
                           controls=("fp8", "tp_local"), **kw)
    one = [REF.served_gaps(SEED + 1, cell.config, reqs, controls=(c,), **kw)
           for c in ("fp8", "tp_local")]
    assert both == (alone, one[0][1] + one[1][1])
    assert REF.served_gaps(SEED + 1, cell.config, reqs, **kw) == (alone, ())
    # a model that is not split over chips has no exchange to leave out
    cell.config.update(tp=1)
    with pytest.raises(ValueError, match="tp_local"):
        RC.compare(cell, SEED + 1, picked, control="tp_local")


def test_tp_local_keeps_the_first_chips_part_of_each_sum(monkeypatch):
    m = Dims.of(T.MODEL_TP4)
    key = W.seed_key(SEED)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, m.d))
    kw = dict(m=m, eps=1e-6, theta=1e6, quant=None)
    full = REF._layer(x, key, jnp.int32(0), parts=1, **kw)
    local = REF._layer(x, key, jnp.int32(0), parts=4, **kw)
    # with the other chips' rows of both projections zeroed, the whole
    # sums are the first chip's parts
    w = W.layer(key, 0, m)
    q, f = m.heads * m.head_dim // 4, m.ffn // 4
    w["wo"] = w["wo"].at[q:].set(0)
    w["w_down"] = w["w_down"].at[f:].set(0)
    monkeypatch.setattr(W, "layer", lambda *a, **k: w)
    zeroed = REF._layer.__wrapped__(x, key, jnp.int32(0), parts=1, **kw)
    np.testing.assert_allclose(np.asarray(local), np.asarray(zeroed),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(full) - np.asarray(local)).max() > 0.1


TP4_RUN = r"""
import json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "benchmarks/chip/tests")
import jax
import chipbench_tiny as T
from benchmarks.chip import run_cell as RC

assert len(jax.devices()) == 4
SEED = int(sys.argv[1])
cell = T.cell("backlog", model=T.MODEL_TP4)
cfg = T.program_cfg(model=T.MODEL_TP4)
out = {}
sound = RC.run(cell, SEED, 1.5, False, cfg=cfg, on_chip=False)
out["sound"] = (sound["correct"], sound["checks"]["served_logit_gap"])
for control in ("fp8", "tp_local"):
    run = RC.run(cell, SEED, 1.5, False, cfg=cfg, on_chip=False,
                 control=control)
    out[control] = (run["correct"], run["checks"]["served_logit_gap"])

# the exchange left out underneath the timed path: every chip's rows of
# the attention output and MLP down projections but the first are zero, so
# the all-reduce after each adds nothing to the first chip's part
real = RC.make_params


def no_exchange(serving, cell, seed, dtype):
    p = real(serving, cell, seed, dtype)
    m, tp = cell.family.dims(cell.config), cell.config["tp"]
    L = p["dense_layers"]
    for leaf, keep in (("wo", m.heads * m.head_dim // tp),
                       ("w_down", m.ffn // tp)):
        L[leaf] = jax.device_put(L[leaf].at[:, keep:].set(0),
                                 L[leaf].sharding)
    return p


RC.make_params = no_exchange
broken = RC.run(cell, SEED, 1.5, False, cfg=cfg, on_chip=False)
out["no_exchange"] = (broken["correct"], broken["checks"]["served_logit_gap"])
print("RESULT " + json.dumps(out))
"""


def test_a_tp4_run_on_four_devices_separates_sound_from_broken():
    """The whole harness at tp 4 on 4 host devices: a sound run is
    correct; the fp8 and tp_local controls in the program's place, and a
    program whose exchange between chips adds nothing, are not."""
    env = {"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
           "HOME": os.environ.get("HOME", str(T.ROOT)),
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", TP4_RUN, str(SEED)],
                       capture_output=True, text=True, env=env,
                       cwd=str(T.ROOT), timeout=600)
    line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
    assert line, r.stdout[-2000:] + r.stderr[-4000:]
    out = json.loads(line[-1][len("RESULT "):])
    ok, gap = out.pop("sound")
    assert ok is True and gap["value"] <= T.TINY_LIMIT, gap
    for name, (ok, gap) in out.items():
        assert ok is False and gap["value"] > T.TINY_LIMIT, (name, gap)
