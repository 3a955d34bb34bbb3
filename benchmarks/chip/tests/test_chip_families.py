"""The qwen2 family module is a thin adapter: through it the harness draws
the same weights, runs the same reference and books the same work as
``weights.py``, ``reference.py`` and ``work.py`` called directly."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench_tiny as T
from benchmarks.chip import reference as REF
from benchmarks.chip import run_cell as RC
from benchmarks.chip import weights as W
from benchmarks.chip import work as WK

SEED = 2 ** 33 + 15
QWEN2 = RC.load_family(T.ROOT, T.MODEL)


def test_the_same_weights_bit_for_bit():
    key = W.seed_key(SEED)
    assert np.array_equal(np.asarray(QWEN2.seed_key(SEED)), np.asarray(key))
    direct = W.program_params(key, WK.Dims.of(T.MODEL), jnp.bfloat16)
    via = QWEN2.program_params(QWEN2.seed_key(SEED), T.MODEL, jnp.bfloat16)
    a, ta = jax.tree.flatten(direct)
    b, tb = jax.tree.flatten(via)
    assert ta == tb
    for x, y in zip(a, b):
        assert x.dtype == y.dtype
        assert np.array_equal(np.asarray(x).view(np.uint16),
                              np.asarray(y).view(np.uint16))


def test_the_same_reference_and_work_counts():
    assert QWEN2.served_gaps is REF.served_gaps
    m = QWEN2.dims(T.MODEL)
    assert m == WK.Dims.of(T.MODEL)
    reqs = [RC.Req(1, 0.0, 600, 8, None), RC.Req(2, 0.0, 513, 8, None)]
    for tp in (1, 4):
        rec = RC.Record(m, tp, 4)
        rec.reqs = {r.rid: r for r in reqs}
        rec.traced_tokens = [(1, 0, 5), (2, 3, 4)]
        RC.book_work(rec, QWEN2)
        want = dict.fromkeys(rec.work, 0)
        want["prefill_flops"] = WK.prefill_flops(m, 600, tp)
        want["prefill_tokens"] = 600
        for plen, js in ((600, range(1, 5)), (513, range(3, 7))):
            for j in js:
                f, b = WK.attn_decode(m, plen + j, tp)
                want["attn_flops"] += f
                want["attn_bytes"] += b
                want["decode_flops"] += WK.decode_token_flops(m, plen + j,
                                                              tp)
                want["decode_tokens"] += 1
        assert rec.work == want


def test_check_refuses_a_program_that_differs_from_the_file():
    QWEN2.check(T.program_cfg(), T.MODEL)
    with pytest.raises(ValueError, match="d_ff"):
        QWEN2.check(T.program_cfg(), dict(T.MODEL, intermediate_size=512))
    with pytest.raises(ValueError, match="qkv_bias"):
        QWEN2.check(dataclasses.replace(T.program_cfg(), qkv_bias=False),
                    T.MODEL)
