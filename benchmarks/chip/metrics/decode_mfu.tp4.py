"""Model FLOPs of the decode tokens delivered in the traced steps (every
layer's matmuls, attention over each token's own context, the vocabulary
projection; a quarter of each on each of the 4 chips), over the device
time of the fused-decode programs, averaged over the chips, times one
chip's bf16 peak."""
from benchmarks.chip import readers

LAYER = "model step"
UNIT = "%"
MOVES = "output_tokens_per_s"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.mfu(trace, rec, readers.DECODE_PROGRAM, "decode_flops")
