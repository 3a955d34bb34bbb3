"""Device time in collectives (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all; a union on each chip) over the traced
window, averaged over the chips. Nothing is read where the trace holds no
collective."""
from benchmarks.chip import readers

LAYER = "device"
UNIT = "%"
MOVES = "output_tokens_per_s"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.collective_share(trace, rec)
