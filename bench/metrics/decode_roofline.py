"""Roofline share of the pooled decode step: the least time the chip needs
for one step's work, over the mean device time of a ``jit__decode``
program in the traced window.  The work is the architecture module's
(``decode_token_flops`` for each row, ``decode_step_bytes`` for the step)
at the traced steps' mean rows and mean live context.  Each decode step
emits one token a live slot, the one it feeds in: the rows are the tokens
emitted in the traced span over the decode programs run in it, and the
token ``j`` of a request is fed at ``prompt + j`` and sees ``prompt + j +
1`` keys."""

import flops

PROGRAM = "jit__decode"


def read(run):
    tr, arch = run.trace, run.arch
    step_bytes = getattr(arch, "decode_step_bytes", None)
    if tr is None or step_bytes is None or run.window.trace_t is None:
        return None
    lo, hi = tr.window()
    ev = [e.dur for e in tr.modules
          if e.name.startswith(PROGRAM) and lo <= e.start <= hi]
    t0, t1 = run.window.trace_t
    ctx = []
    for uid, req in run.window.reqs.items():
        ctx += [len(req.prompt) + j + 1 for j, t in
                enumerate(run.window.tok_t[uid]) if t0 <= t <= t1]
    if not ev or not ctx:
        return None
    rows, mean_ctx = len(ctx) / len(ev), sum(ctx) / len(ctx)
    least, _ = flops.least_time(
        rows * arch.decode_token_flops(run.model, mean_ctx),
        step_bytes(run.model, rows, mean_ctx), run.peaks)
    return 100.0 * least / (sum(ev) / len(ev) * 1e-9)
