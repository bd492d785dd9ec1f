"""Device-idle time at the head of each ``ServeEngine.step()`` that runs a
decode: from the step's start to its decode program's start (admission and
``serve.launch``), mean over the traced window's steps."""

import step_split


def read(run):
    split = step_split.idle_split(run.trace)
    if not split:
        return None
    return sum(b for b, _ in split) / len(split) * 1e-6
