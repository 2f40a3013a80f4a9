"""Device busy time per request less kernels and collectives, mean over
chips (ms): XLA's gathers, scatter-max, concatenates and copies."""
from bench.trace import mean


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    ns = mean(c.busy_ns - c.kernel_ns - c.collective_ns
              for c in run.trace.chips.values())
    return ns / 1e6 / len(run.requests)
