"""Share of the traced window in which no operation ran on the device."""

import devtrace


def read(run):
    if run.trace is None or not (run.trace.ops or run.trace.modules):
        return None
    lo, hi = run.trace.window()
    if hi <= lo:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(run.trace, lo, hi) / (hi - lo))
