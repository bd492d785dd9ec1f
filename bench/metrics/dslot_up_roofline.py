"""Roofline share of the digit-serial MLP up-projection kernel: for its
events in the traced window, the least time the chip needs for the dense
bf16 up-projection at the rows each call receives (``flops.up_proj_work``),
over the summed device time of those events."""

import devtrace
import flops

KERNEL = "dslot_matmul_pallas"   # the kernel's op name in the trace today


def read(run):
    tr = run.trace
    if tr is None:
        return None
    lo, hi = tr.window()
    m = run.model
    mods = sorted(tr.modules, key=lambda e: e.start)
    least = spent = 0.0
    for e in tr.ops:
        # by the op's own name: other ops name the kernel among operands
        if devtrace.op_name(e.name) != KERNEL or not lo <= e.start <= hi:
            continue
        mod = devtrace.enclosing(mods, e.start)
        rows = run.rows(mod.name) if mod is not None else None
        if rows is None:
            continue
        t, _ = flops.least_time(*flops.up_proj_work(
            rows, m["d_model"], m["d_ff"]), run.peaks)
        least += t
        spent += e.dur * 1e-9
    return 100.0 * least / spent if spent else None
