"""Rows that delivered decode tokens in a step, over ``max_batch``,
averaged over the steps of the window that decoded. Counted from the
runtime's token log."""
from benchmarks.chip import readers

LAYER = "runtime batching"
UNIT = "%"
MOVES = "output_tokens_per_s"
SOURCE = "program_counter"


def read(trace, rec):
    return readers.occupancy(trace, rec)
