"""Reading the profiler's trace into device events and host spans.

A trace is reduced to three lists, all on the profiler's one clock (ns):

* ``modules``: executions of compiled programs on the device (the "XLA
  Modules" line of each device plane), e.g. ``jit__decode``;
* ``ops``: operations inside them (the "XLA Ops" line), kernels included;
* ``spans``: the benchmark's own host spans (``bench.*`` annotations).

Device numbers are averaged over the device planes found.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field


@dataclass
class Event:
    name: str
    start: float            # ns
    end: float              # ns
    device: int = 0

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    modules: list = field(default_factory=list)
    ops: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    n_devices: int = 1

    def window(self) -> tuple[float, float]:
        """From the first to the last step span the trace holds."""
        steps = [s for s in self.spans if s.name == "bench.step"]
        if not steps:
            return 0.0, 0.0
        return min(s.start for s in steps), max(s.end for s in steps)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    tr, dev = Trace(), 0
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            found = False
            for line in plane.lines:
                dest = {"XLA Modules": tr.modules,
                        "XLA Ops": tr.ops}.get(line.name)
                if dest is None:
                    continue
                events = [Event(e.name, e.start_ns, e.end_ns, dev)
                          for e in line.events]
                dest.extend(events)
                found = found or bool(events)
            dev += found        # planes that ran no program are not chips
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend(Event(e.name, e.start_ns, e.end_ns)
                                for e in line.events
                                if e.name.startswith("bench."))
    tr.n_devices = max(1, dev)
    return tr


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged intervals clipped to [lo, hi]."""
    iv = sorted((max(a, lo), min(b, hi)) for a, b in intervals
                if b > lo and a < hi)
    out: list[list[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace, lo: float, hi: float) -> float:
    """Time in [lo, hi] in which an operation ran, averaged over devices."""
    events = tr.ops or tr.modules
    total = 0.0
    for d in range(tr.n_devices):
        total += sum(b - a for a, b in union(
            ((e.start, e.end) for e in events if e.device == d), lo, hi))
    return total / tr.n_devices


def enclosing(events: list, t: float):
    """The event of ``events`` (sorted by start) that contains time t."""
    for e in events:
        if e.start <= t <= e.end:
            return e
        if e.start > t:
            return None
    return None


def op_name(name: str) -> str:
    """An operation's short name: ``%fusion.12 = bf16[..] fusion(..)`` is
    ``fusion``; instances of one operation share it."""
    head = name.split(" = ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


CONTAINERS = ("while", "conditional", "call")   # hold other ops' time


def breakdown(tr: Trace, lo: float, hi: float, top: int = 10) -> dict:
    """Device operations by total time (loops that contain other
    operations left out), and the longest idle gaps named by the host span
    that was open in the middle of each."""
    per_op: dict[str, float] = {}
    for e in tr.ops:
        name = op_name(e.name)
        if lo <= e.start <= hi and name not in CONTAINERS:
            per_op[name] = per_op.get(name, 0.0) + e.dur / tr.n_devices
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    busy = union(((e.start, e.end) for e in (tr.ops or tr.modules)
                  if e.device == 0), lo, hi)
    gaps, prev = [], lo
    for a, b in busy + [(hi, hi)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    spans = sorted(tr.spans, key=lambda s: s.start)
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2
        inner = [s for s in spans if s.start <= mid <= s.end]
        label = min(inner, key=lambda s: s.dur).name if inner else "no span"
        named.append([label, (b - a) * 1e-9])
    return {"device_ops": [[n, t * 1e-9] for n, t in ops],
            "idle_gaps": named}
