"""Share of its roofline that the local coloring step reaches (%).

Numerator: the least time, ``bench.roofline.least_bytes`` of each
request (spread over the chips) at the chip's peak HBM bandwidth.
Denominator: the device time of the local step, busy time less
collectives (``xla_ms + kernel_ms``), mean over chips.  Bandwidth bounds
this step: it does no arithmetic worth a FLOP count.
"""
from bench.trace import mean


def read(run):
    if run.trace is None or not run.trace.chips or run.peaks is None:
        return None
    local_ns = mean(c.busy_ns - c.collective_ns
                    for c in run.trace.chips.values())
    if local_ns <= 0:
        return None
    least = sum(r["least_bytes"] for r in run.requests) / run.chips
    least_ns = least / run.peaks["hbm_bytes_per_s"] * 1e9
    return least_ns / local_ns * 100.0
