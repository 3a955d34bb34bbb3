"""90th percentile, over the requests due in the window, of the time from
when a request was due to the start of the ``DecodeRuntime.step()`` that
admitted it (its first token came out of that step). Host clock."""
from benchmarks.chip import readers

LAYER = "runtime admission"
UNIT = "s"
MOVES = "ttft_p90_s"
SOURCE = "host_clock"


def read(trace, rec):
    return readers.queue_wait_p90(trace, rec)
