"""Model FLOPs of the decode tokens delivered in the traced steps (every
layer's matmuls, attention over each token's own context, the vocabulary
projection; per chip), over the device time of the fused-decode programs
times the chip's bf16 peak."""
from benchmarks.chip import readers

LAYER = "model step"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.mfu(trace, rec, readers.DECODE_PROGRAM, "decode_flops")
