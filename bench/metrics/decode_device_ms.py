"""Device time of one pooled decode program (``jit__decode``), mean over
the traced window."""

PROGRAM = "jit__decode"


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window()
    ev = [e.dur for e in run.trace.modules
          if e.name.startswith(PROGRAM) and lo <= e.start <= hi]
    return sum(ev) / len(ev) * 1e-6 if ev else None
