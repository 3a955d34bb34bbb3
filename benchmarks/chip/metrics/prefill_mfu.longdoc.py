"""Model FLOPs of the requested prompt tokens admitted in the traced steps
(not the pad up to the length bucket; causal attention; one vocabulary row
per request), over the device time of the admission programs times the
chip's bf16 peak."""
from benchmarks.chip import readers

LAYER = "model step"
UNIT = "%"
MOVES = "output_tokens_per_s"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.mfu(trace, rec, readers.ADMIT_PROGRAM, "prefill_flops")
