"""What the per-layer metric files (``metrics/<name>.py``) share.

Each reader takes the reduced trace (``trace.Trace``, or None in a run
without the profiler) and the run's record (``run_cell.Record``) and
returns a number, or None where it finds nothing to read. Work comes from
``work.py`` over the steps inside the traced window only; device times are
averaged over the replica's chips, so a tensor-parallel share is per chip.
A share of a peak or a roofline counts only the work the traffic asked
for, so padding and dead rows lower it and nothing can lift it over 100%.
"""
from __future__ import annotations

from benchmarks.chip import work as WK

DECODE_PROGRAM = "jit_block"      # RuntimeKernels.decode_fn's fused block
ADMIT_PROGRAM = "jit_admit"       # RuntimeKernels.admit_fn's prefill
PAGED_KERNEL = "paged_decode_attention"


def _pct(num: float, den: float):
    return 100.0 * num / den if den > 0 and num > 0 else None


def mfu(trace, rec, program: str, flops_key: str):
    if trace is None:
        return None
    t = trace.mean(lambda c: trace.program_s(c, program))
    return _pct(rec.work[flops_key], t * rec.peak["bf16_flops_per_s"])


def kernel_roofline(trace, rec, kernel: str, flops_key: str,
                    bytes_key: str):
    if trace is None:
        return None
    t = trace.mean(lambda c: trace.op_s(c, kernel))
    least = WK.roofline_s(rec.work[flops_key], rec.work[bytes_key], rec.peak)
    return _pct(least, t)


def idle_share(trace, rec):
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s() / trace.window_s)


def collective_share(trace, rec):
    if trace is None or trace.window_s <= 0:
        return None
    return _pct(trace.mean(trace.collective_s), trace.window_s)


def queue_wait_p90(trace, rec):
    waits = [r.admit - r.due for r in (rec.reqs[i] for i in rec.in_window)
             if r.admit is not None]
    return rec.q(waits, 90)


def occupancy(trace, rec):
    if not rec.occupancy:
        return None
    return 100.0 * sum(rec.occupancy) / len(rec.occupancy)
