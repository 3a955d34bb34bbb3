"""Pallas kernel validation: shape/dtype sweeps + hypothesis properties,
all against the pure-jnp oracles in repro.kernels.ref (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # clean env: deterministic fallback
    from _hypothesis_fallback import given, settings, strategies as st

from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.decode_attention import decode_attention_kernel
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mlstm_scan import mlstm_chunkwise_kernel
from repro.kernels.paged_decode_attention import paged_decode_attention_kernel
from repro.kernels.ssm_scan import ssm_scan_kernel
from repro.models.attention import blockwise_attention, decode_attention
from repro.models.xlstm import mlstm_chunkwise


def tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


# --------------------------------------------------------- flash attention

SWEEP = [
    # B, Hq, Hkv, Sq, Sk, dh, causal, window, chunk, dtype
    (2, 4, 2, 128, 128, 64, True, None, None, jnp.float32),
    (1, 8, 1, 256, 256, 128, True, None, None, jnp.float32),
    (2, 4, 4, 128, 256, 64, False, None, None, jnp.float32),
    (1, 2, 2, 256, 256, 64, True, 64, None, jnp.float32),
    (1, 2, 1, 256, 256, 64, True, None, 128, jnp.float32),
    (2, 4, 2, 128, 128, 64, True, None, None, jnp.bfloat16),
    (1, 4, 2, 384, 384, 32, True, 128, None, jnp.float32),
]


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Sk,dh,causal,window,chunk,dtype", SWEEP)
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Sk, dh, causal, window,
                               chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, Sq, dh), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, dh), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window, chunk=chunk,
                          interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal, window=window, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol(dtype))


@settings(max_examples=12, deadline=None)
@given(data=st.data())
def test_flash_attention_property(data):
    """Property: kernel == oracle across random GQA geometries, and output
    rows are convex combinations of V rows (|out| <= max |v|)."""
    B = data.draw(st.integers(1, 2))
    Hkv = data.draw(st.sampled_from([1, 2]))
    G = data.draw(st.sampled_from([1, 2, 4]))
    S = data.draw(st.sampled_from([128, 256]))
    dh = data.draw(st.sampled_from([32, 64]))
    causal = data.draw(st.booleans())
    ks = jax.random.split(jax.random.PRNGKey(data.draw(st.integers(0, 99))), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, S, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, dh), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = R.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-4, rtol=2e-4)
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4


def test_blockwise_jnp_matches_naive():
    """The lowering-path jnp attention equals the naive oracle too."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    B, Hq, Hkv, S, dh = 2, 4, 2, 192, 32
    q = jax.random.normal(ks[0], (B, S, Hq, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, dh), jnp.float32)
    out = blockwise_attention(q, k, v, causal=True, window=64, block_kv=64)
    ref = R.attention_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True, window=64)
    np.testing.assert_allclose(out.transpose(0, 2, 1, 3), ref,
                               atol=2e-4, rtol=2e-4)


# ----------------------------------------------------------------- mLSTM

@pytest.mark.parametrize("B,S,H,dh,chunk,dtype", [
    (2, 256, 2, 64, 64, jnp.float32),
    (1, 128, 4, 32, 32, jnp.float32),
    (2, 128, 2, 64, 64, jnp.bfloat16),
    (1, 192, 1, 128, 64, jnp.float32),
])
def test_mlstm_kernel_sweep(B, S, H, dh, chunk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, S, H, dh), dtype)
    k = jax.random.normal(ks[1], (B, S, H, dh), dtype) * dh ** -0.5
    v = jax.random.normal(ks[2], (B, S, H, dh), dtype)
    li = jax.random.normal(ks[3], (B, S, H), jnp.float32)
    lf = jax.random.normal(ks[4], (B, S, H), jnp.float32) + 2.0
    h_ref, (C_r, n_r, m_r) = R.mlstm_ref(q, k, v, li, lf)
    h_ker, (C_k, n_k, m_k) = mlstm_chunkwise_kernel(
        q, k, v, li, lf, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(h_ker, np.float32),
                               np.asarray(h_ref, np.float32), **tol(dtype))
    np.testing.assert_allclose(C_k, C_r, atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(m_k, m_r, atol=1e-4, rtol=1e-4)


def test_mlstm_state_handoff_prefill_to_decode():
    """Kernel prefill state continues exactly via the decode recurrence."""
    from repro.models.xlstm import mlstm_decode
    B, S, H, dh = 1, 128, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q = jax.random.normal(ks[0], (B, S + 1, H, dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S + 1, H, dh), jnp.float32) * dh ** -0.5
    v = jax.random.normal(ks[2], (B, S + 1, H, dh), jnp.float32)
    li = jax.random.normal(ks[3], (B, S + 1, H), jnp.float32)
    lf = jax.random.normal(ks[4], (B, S + 1, H), jnp.float32)
    h_all, _ = R.mlstm_ref(q, k, v, li, lf)
    _, state = mlstm_chunkwise_kernel(q[:, :S], k[:, :S], v[:, :S],
                                      li[:, :S], lf[:, :S], chunk=32,
                                      interpret=True)
    h1, _ = mlstm_decode(q[:, S], k[:, S], v[:, S], li[:, S], lf[:, S], state)
    np.testing.assert_allclose(h1, h_all[:, S], atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------------- SSM

@pytest.mark.parametrize("B,S,di,N,chunk,bdi", [
    (2, 256, 512, 16, 64, 256),
    (1, 128, 256, 8, 32, 128),
    (2, 64, 128, 16, 64, 64),
])
def test_ssm_kernel_sweep(B, S, di, N, chunk, bdi):
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    u = jax.random.normal(ks[0], (B, S, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, N)))
    Bs = jax.random.normal(ks[3], (B, S, N))
    Cs = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (di,))
    y_ref, h_ref = R.ssm_ref(u, dt, A, Bs, Cs, D)
    y_ker, h_ker = ssm_scan_kernel(u, dt, A, Bs, Cs, D, chunk=chunk,
                                   block_di=bdi, interpret=True)
    np.testing.assert_allclose(y_ker, y_ref, atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(h_ker, h_ref, atol=2e-4, rtol=2e-3)


def test_ssm_kernel_matches_model_associative_scan():
    from repro.models.hybrid import ssm_scan
    B, S, di, N = 1, 128, 128, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    u = jax.random.normal(ks[0], (B, S, di), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, di)))
    A = -jnp.exp(jax.random.normal(ks[2], (di, N)))
    Bs = jax.random.normal(ks[3], (B, S, N))
    Cs = jax.random.normal(ks[4], (B, S, N))
    D = jax.random.normal(ks[5], (di,))
    y_model, h_model = ssm_scan(u, dt, A, Bs, Cs, D)
    y_ker, h_ker = ssm_scan_kernel(u, dt, A, Bs, Cs, D, chunk=32,
                                   block_di=128, interpret=True)
    np.testing.assert_allclose(y_ker, y_model, atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(h_ker, h_model, atol=2e-4, rtol=2e-3)


# --------------------------------------------------------- decode attention

@pytest.mark.parametrize("B,Hq,Hkv,Smax,dh,bk,window,chunk", [
    (3, 8, 2, 1024, 64, 256, None, None),
    (2, 4, 1, 512, 128, 128, 128, None),
    (2, 2, 2, 512, 64, 256, None, 256),
])
def test_decode_attention_sweep(B, Hq, Hkv, Smax, dh, bk, window, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Hq, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, dh), jnp.float32)
    lens = jnp.asarray(np.linspace(3, Smax, B).astype(np.int32))
    o_ref = R.decode_attention_ref(q, kc, vc, lengths=lens, window=window,
                                   chunk=chunk)
    o_ker = decode_attention_kernel(q, kc, vc, lens, window=window,
                                    chunk=chunk, block_k=bk, interpret=True)
    np.testing.assert_allclose(o_ker, o_ref, atol=2e-5, rtol=2e-5)


def test_decode_attention_per_row_pos_kernel_parity():
    """The slab layout's per-row position vector (not just scalar pos):
    Pallas decode kernel (interpret) == jnp model decode attention."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    B, Hq, Hkv, Smax, dh = 4, 4, 2, 256, 32
    q = jax.random.normal(ks[0], (B, 1, Hq, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, dh), jnp.float32)
    pos = jnp.asarray([3, 77, 130, 255], jnp.int32)        # per-row depths
    o_jnp = decode_attention(q, kc, vc, pos=pos)
    o_skip = decode_attention(q, kc, vc, pos=pos, block_skip=64)
    o_ker = ops.decode_attention(q, kc, vc, pos + 1, block_k=64)
    np.testing.assert_allclose(o_ker, o_jnp, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(o_skip, o_jnp, atol=2e-5, rtol=2e-5)


def _paged_case(seed, B=3, Hq=4, Hkv=2, dh=16, ps=8, P=4, n_pages=16):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(B, Hq, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(n_pages, Hkv, ps, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(n_pages, Hkv, ps, dh)), jnp.float32)
    lengths = rng.integers(1, P * ps + 1, B)
    pages = np.zeros((B, P), np.int32)
    nxt = 1                      # page 0 stays the null page
    for b in range(B):
        for j in range(-(-int(lengths[b]) // ps)):
            pages[b, j] = nxt
            nxt += 1
    assert nxt <= n_pages
    return q, kp, vp, jnp.asarray(pages), jnp.asarray(lengths, jnp.int32)


def _gather(pool, pages):
    """Head-major pool pages -> each row's dense (B, P*ps, Hkv, dh) cache."""
    B, P = pages.shape
    g = pool[pages].transpose(0, 1, 3, 2, 4)            # (B, P, ps, Hkv, dh)
    return g.reshape(B, P * pool.shape[2], pool.shape[1], pool.shape[3])


@pytest.mark.parametrize("window,chunk", [(None, None), (11, None),
                                          (None, 16)])
def test_paged_decode_attention_kernel_vs_gathered_ref(window, chunk):
    """Paged kernel (scalar-prefetch page table, per-row early exit over
    the page grid) == gather-the-pages-then-dense-oracle."""
    q, kp, vp, pages, lengths = _paged_case(0)
    out = paged_decode_attention_kernel(q, kp, vp, pages, lengths,
                                        window=window, chunk=chunk,
                                        interpret=True)
    kb, vb = _gather(kp, pages), _gather(vp, pages)
    ref = R.decode_attention_ref(q, kb, vb, lengths=lengths, window=window,
                                 chunk=chunk)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@settings(max_examples=8, deadline=None)
@given(data=st.data())
def test_paged_decode_attention_property(data):
    seed = data.draw(st.integers(0, 99))
    B = data.draw(st.integers(1, 4))
    ps = data.draw(st.sampled_from([4, 8, 16]))
    P = data.draw(st.sampled_from([2, 4]))
    q, kp, vp, pages, lengths = _paged_case(seed, B=B, ps=ps, P=P,
                                            n_pages=B * P + 2)
    out = paged_decode_attention_kernel(q, kp, vp, pages, lengths,
                                        interpret=True)
    kb, vb = _gather(kp, pages), _gather(vp, pages)
    ref = R.decode_attention_ref(q, kb, vb, lengths=lengths)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_kernel_mode_routes_paged_dispatch():
    """The kernel_mode toggle end-to-end at the ops layer: pallas
    (interpret on CPU) and jnp must produce matching outputs for the
    slab layout's per-row lengths, and auto must resolve per backend."""
    q, kp, vp, pages, lengths = _paged_case(3)
    q4 = q[:, None]                           # ops layer takes (B,1,Hq,dh)
    try:
        ops.set_kernel_mode("jnp")
        assert ops.resolved_mode() == "jnp" and not ops.use_kernels()
        o_jnp = ops.decode_attention_paged(q4, kp, vp, pages, lengths,
                                           kv_bucket=32, page_size=8)
        ops.set_kernel_mode("pallas")
        assert ops.use_kernels()
        o_pal = ops.decode_attention_paged(q4, kp, vp, pages, lengths,
                                           kv_bucket=32, page_size=8)
        ops.set_kernel_mode("auto")
        assert ops.resolved_mode() == ("pallas" if ops.on_tpu() else "jnp")
    finally:
        ops.set_kernel_mode(None)
    np.testing.assert_allclose(o_pal, o_jnp, atol=2e-5, rtol=2e-5)
    with pytest.raises(ValueError):
        ops.set_kernel_mode("cuda")


@pytest.mark.parametrize("width", [1, 3])
@pytest.mark.parametrize("softcap", [0.0, 5.0])
def test_paged_ops_pallas_matches_jnp(width, softcap):
    """Pallas mode runs the kernel for every paged dispatch — soft-capped
    logits included, with no quiet drop to jnp — and matches the jnp
    path: the 1-token decode and the W-token window (spec verify / tail
    prefill) over the same pool."""
    q, kp, vp, pages, lengths = _paged_case(5)
    qw = jnp.stack([q * (1.0 + 0.1 * w) for w in range(width)], axis=1)
    pos = jnp.maximum(lengths - width, 0)
    kw = dict(kv_bucket=32, page_size=8, softcap=softcap)
    outs = {}
    try:
        for mode in ("jnp", "pallas"):
            ops.set_kernel_mode(mode)
            outs[mode] = (ops.window_attention_paged(qw, kp, vp, pages, pos,
                                                     **kw) if width > 1 else
                          ops.decode_attention_paged(qw, kp, vp, pages,
                                                     lengths, **kw))
    finally:
        ops.set_kernel_mode(None)
    np.testing.assert_allclose(outs["pallas"], outs["jnp"], atol=2e-5,
                               rtol=2e-5)


def test_decode_step_paged_pallas_vs_jnp():
    """Model-level parity: one paged transformer decode step under
    kernel_mode=pallas (interpret) matches kernel_mode=jnp — logits and
    the KV written into the pool."""
    from repro.configs.base import get_config
    from repro.models import model_api as MA
    from repro.models import transformer
    cfg = get_config("qwen2-7b").reduced()
    params = transformer.init(jax.random.PRNGKey(0), cfg)
    rows, ps, n_pages = 3, 8, 8
    pages = np.zeros((rows, 3), np.int32)
    pages[0, :2] = [1, 2]
    pages[1, :1] = [3]
    cache = MA.init_paged_cache(cfg, rows, n_pages, ps)
    cache["pos"] = jnp.asarray([9, 4, 0], jnp.int32)
    tok = jnp.asarray([[7], [11], [0]], jnp.int32)
    outs = {}
    try:
        for mode in ("jnp", "pallas"):
            ops.set_kernel_mode(mode)
            logits, new_cache = transformer.decode_step(
                params, tok, dict(cache), cfg, pages=jnp.asarray(pages),
                kv_bucket=16)
            outs[mode] = (logits, new_cache["dense"]["k"])
    finally:
        ops.set_kernel_mode(None)
    np.testing.assert_allclose(outs["pallas"][0], outs["jnp"][0],
                               atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(outs["pallas"][1], outs["jnp"][1],
                               atol=2e-5, rtol=2e-5)


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_decode_attention_property(data):
    B = data.draw(st.integers(1, 3))
    Hkv = data.draw(st.sampled_from([1, 2]))
    G = data.draw(st.sampled_from([1, 3]))
    Smax = data.draw(st.sampled_from([256, 512]))
    dh = data.draw(st.sampled_from([32, 64]))
    seed = data.draw(st.integers(0, 99))
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Hkv * G, dh), jnp.float32)
    kc = jax.random.normal(ks[1], (B, Smax, Hkv, dh), jnp.float32)
    vc = jax.random.normal(ks[2], (B, Smax, Hkv, dh), jnp.float32)
    lens = jnp.asarray(
        np.random.default_rng(seed).integers(1, Smax + 1, B), jnp.int32)
    o_ref = R.decode_attention_ref(q, kc, vc, lengths=lens)
    o_ker = decode_attention_kernel(q, kc, vc, lens, block_k=128,
                                    interpret=True)
    np.testing.assert_allclose(o_ker, o_ref, atol=2e-5, rtol=2e-5)
