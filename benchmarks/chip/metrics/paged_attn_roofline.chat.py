"""Least time of the paged attention the delivered decode tokens needed
(the larger of its FLOPs over the bf16 peak and its K, V, q and output
bytes over HBM bandwidth, for each token's own context, not the kv bucket
or pages the grid walks), over the device time of the
``paged_decode_attention`` kernel. Per chip."""
from benchmarks.chip import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.kernel_roofline(trace, rec, readers.PAGED_KERNEL,
                                   "attn_flops", "attn_bytes")
