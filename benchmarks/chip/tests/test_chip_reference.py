"""The float32 reference against the program's own uncached forward, and
the seeded weights drawn layer by layer against the stacked draw."""
import jax
import jax.numpy as jnp
import numpy as np

import chipbench_tiny as T
from benchmarks.chip import reference as REF
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
