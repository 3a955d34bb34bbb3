"""Operation and byte counts of the served work, by hand."""
import numpy as np
import pytest

import chipbench_tiny  # noqa: F401
from benchmarks.chip import work as WK

M = WK.Dims(d=8, layers=2, heads=4, kv_heads=2, head_dim=2, ffn=16,
            vocab=32)


def test_layer_matmul_params():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 each
    assert M.layer_matmul_params == 64 + 32 + 32 + 64 + 3 * 128


def test_decode_attention_by_hand():
    flops, byts = WK.attn_decode(M, ctx=10)
    assert flops == 4 * 10 * 4 * 2 * 2          # qk and pv, 4 heads, 2 layers
    # K and V of 10 entries x 2 kv heads x dim 2, plus q and out of 4 heads
    assert byts == (2 * 10 * 2 * 2 + 2 * 4 * 2) * 2 * 2
    f4, b4 = WK.attn_decode(M, ctx=10, tp=2)
    assert (f4, b4) == (flops // 2, byts // 2)


def test_decode_and_prefill_flops_by_hand():
    dense = 2 * M.layer_matmul_params * M.layers
    assert WK.decode_token_flops(M, 10) == \
        dense + 2 * 8 * 32 + WK.attn_decode(M, 10)[0]
    attn = 4 * 4 * 2 * 2 * (3 * 4 // 2)        # 3 tokens: 1 + 2 + 3 entries
    assert WK.prefill_flops(M, 3) == 3 * dense + attn + 2 * 8 * 32


def test_padding_never_counts():
    # work is a function of the requested tokens and each token's own
    # context alone: no argument carries a bucket, a page or a pad row
    assert WK.prefill_flops(M, 600) < WK.prefill_flops(M, 1024)
    assert WK.attn_decode(M, 600)[1] < WK.attn_decode(M, 1024)[1]


def _rec(reqs, traced, peak=None):
    from benchmarks.chip import run_cell as RC
    rec = RC.Record(M, 1, 4, peak=peak or {})
    rec.reqs = {r.rid: r for r in reqs}
    rec.traced_tokens = traced
    RC.book_work(rec)
    return rec


def _req(rid, plen, bucket):
    from benchmarks.chip import run_cell as RC
    return RC.Req(rid, 0.0, plen, 8, np.zeros(bucket, np.int32))


def test_padding_is_not_booked():
    # the same request, prefilled padded from 600 to 1024 or unpadded,
    # books the same prefill and decode work: 600 + j entries of context
    padded = _rec([_req(1, 600, 1024)], [(1, 0, 5)]).work
    bare = _rec([_req(1, 600, 600)], [(1, 0, 5)]).work
    assert padded == bare
    f, b = WK.attn_decode(M, 600 + 4)
    assert padded["decode_tokens"] == 4
    assert padded["prefill_flops"] == WK.prefill_flops(M, 600)
    assert padded["attn_bytes"] == sum(WK.attn_decode(M, 600 + j)[1]
                                       for j in range(1, 5))
    assert padded["attn_bytes"] < _rec([_req(1, 1024, 1024)],
                                       [(1, 0, 5)]).work["attn_bytes"]


def test_roofline_share_cannot_pass_the_kernel_time():
    from benchmarks.chip import readers
    from benchmarks.chip import trace as TRC
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    # tokens 1..4 of request 1 after its prefill, then 3 more of request 2
    rec = _rec([_req(1, 600, 1024), _req(2, 300, 512)],
               [(1, 1, 4), (2, 5, 3)], peak)
    least = WK.roofline_s(rec.work["attn_flops"], rec.work["attn_bytes"],
                          peak)
    assert least == pytest.approx(
        sum(WK.attn_decode(M, 600 + j)[1] for j in range(1, 5)) / 1e9
        + sum(WK.attn_decode(M, 300 + j)[1] for j in range(5, 8)) / 1e9)

    def trace(kernel_s):
        ops = [TRC.Op(0, "paged_decode_attention.3", "jit_block", 0.5,
                      kernel_s),
               TRC.Op(0, "fusion.1", "jit_block", 0.5 + kernel_s, 0.1)]
        return TRC.Trace(ops, [], (0.0, 10.0), [0], [])

    # a kernel that took its least time reads 100%; twice that, 50%
    assert readers.kernel_roofline(trace(least), rec,
                                   readers.PAGED_KERNEL, "attn_flops",
                                   "attn_bytes") == pytest.approx(100.0)
    assert readers.kernel_roofline(trace(2 * least), rec,
                                   readers.PAGED_KERNEL, "attn_flops",
                                   "attn_bytes") == pytest.approx(50.0)
    # a trace without the kernel reads nothing, never 0
    none = TRC.Trace([TRC.Op(0, "fusion.1", "jit_block", 0.5, 0.1)], [],
                     (0.0, 10.0), [0], [])
    assert readers.kernel_roofline(none, rec, readers.PAGED_KERNEL,
                                   "attn_flops", "attn_bytes") is None


def test_unknown_device_is_an_error():
    assert WK.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        WK.peaks("TPU v9 imaginary")
