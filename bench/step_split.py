"""Each ``ServeEngine.step()`` of a trace split at its pooled decode
program: the device-idle time from the step's start to the program's start
(the engine's ``serve.admit`` and ``serve.launch`` phases) and from the
program's end to the step's end (``serve.readback`` and ``serve.emit``).

A step is the benchmark's ``bench.step`` span, which holds nothing but the
``step()`` call (the program's own ``serve.step`` span inside it); the
steps that run no decode program are left out.
"""

from __future__ import annotations

import bisect

import devtrace

STEP = "bench.step"
PROGRAM = "jit__decode"


def idle_split(tr) -> list[tuple[float, float]]:
    """(idle before the decode program, idle after it) in ns, one pair per
    step span of the trace that holds a decode program."""
    if tr is None or not (tr.ops or tr.modules):
        return []
    progs = sorted((m for m in tr.modules
                    if m.name.startswith(PROGRAM) and m.device == 0),
                   key=lambda m: m.start)
    starts = [m.start for m in progs]
    out = []
    for s in tr.spans:
        if s.name != STEP:
            continue
        i = bisect.bisect_left(starts, s.start)
        if i == len(progs) or progs[i].end > s.end:
            continue
        p = progs[i]
        out.append(((p.start - s.start) - devtrace.busy_ns(tr, s.start,
                                                           p.start),
                    (s.end - p.end) - devtrace.busy_ns(tr, p.end, s.end)))
    return out
