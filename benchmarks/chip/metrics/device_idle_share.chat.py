"""1 - (union of the device's operation intervals) / (traced window),
averaged over the chips."""
from benchmarks.chip import readers

LAYER = "device"
UNIT = "%"
MOVES = "tpot_p90_ms"
SOURCE = "device_trace"


def read(trace, rec):
    return readers.idle_share(trace, rec)
