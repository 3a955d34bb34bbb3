"""Least time of the paged attention the delivered decode tokens needed on
one chip (its 7 query heads and 1 KV head: the larger of the FLOPs over
the bf16 peak and the K, V, q and output bytes over HBM bandwidth, for
each token's own context), over the device time of the
``paged_decode_attention`` kernel under ``shard_map``, averaged over the
chips."""
from benchmarks.chip import readers

LAYER = "kernels"
UNIT = "%"
MOVES = "output_tokens_per_s"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.kernel_roofline(trace, rec, readers.PAGED_KERNEL,
                                   "attn_flops", "attn_bytes")
