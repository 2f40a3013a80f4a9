"""Share of the traced window in which the most idle chip runs no
operation (%)."""


def read(run):
    if run.trace is None or not run.trace.chips:
        return None
    busy = min(c.busy_ns for c in run.trace.chips.values())
    return (1.0 - busy / run.trace.window_ns) * 100.0
