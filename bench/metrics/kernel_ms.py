"""Device time of the Mosaic kernels per request, mean over chips (ms)."""
from bench.trace import mean


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    ns = mean(c.kernel_ns for c in run.trace.chips.values())
    return ns / 1e6 / len(run.requests)
