"""Host time of one ``ServeEngine.step()``: its span less the device busy
time inside it, mean over the steps of the traced window."""

import devtrace


def read(run):
    if run.trace is None or not (run.trace.ops or run.trace.modules):
        return None
    steps = [s for s in run.trace.spans if s.name == "bench.step"]
    if not steps:
        return None
    host = [s.dur - devtrace.busy_ns(run.trace, s.start, s.end)
            for s in steps]
    return sum(host) / len(host) * 1e-6
