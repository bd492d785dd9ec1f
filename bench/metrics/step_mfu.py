"""Whole-step model FLOP utilization: the model operations of the prompt
tokens and output tokens processed in the traced span (the window when
untraced), over that span times the chip's bf16 peak."""

import serving


def read(run):
    w = run.window
    lo, hi = w.trace_t if w.trace_t else (w.t0, w.t1)
    if hi <= lo:
        return None
    ops = serving.work_in(w, run.arch, run.model, lo, hi)
    return 100.0 * ops / ((hi - lo) * run.peaks["bf16_flops"])
