"""Device-idle time at the tail of each ``ServeEngine.step()`` that runs a
decode: from its decode program's end to the step's end (``serve.readback``
and ``serve.emit``), mean over the traced window's steps."""

import step_split


def read(run):
    split = step_split.idle_split(run.trace)
    if not split:
        return None
    return sum(a for _, a in split) / len(split) * 1e-6
