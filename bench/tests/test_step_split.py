"""The engine-host readers: each ``step()`` split at its decode program.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import glob
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import devtrace  # noqa: E402
import run  # noqa: E402
import serving  # noqa: E402
import step_split  # noqa: E402
import tiny  # noqa: E402

SEED = 2 ** 31 + 4099
MS = 1e6
READERS = ("engine_launch_idle_ms", "engine_readback_idle_ms")


def _trace():
    """Three steps: 0-12 ms (an admission op at 0.5-1, decode 2-10), 12-40
    ms (decode 15-35), 40-45 ms (no decode program: a stalled step)."""
    E = devtrace.Event
    return devtrace.Trace(
        modules=[E("jit__extend_lanes", 0.5 * MS, 1 * MS),
                 E("jit__decode", 2 * MS, 10 * MS),
                 E("jit__decode", 15 * MS, 35 * MS)],
        ops=[E("%fusion.1 = f32[] fusion()", 0.5 * MS, 1 * MS),
             E("%copy.2 = f32[] copy()", 2 * MS, 6 * MS),
             E("%fusion.3 = f32[] fusion()", 6 * MS, 10 * MS),
             E("%copy.2 = f32[] copy()", 15 * MS, 35 * MS)],
        spans=[E("bench.step", 0, 12 * MS), E("bench.step", 12 * MS, 40 * MS),
               E("bench.step", 40 * MS, 45 * MS),
               E("bench.submit", 45 * MS, 46 * MS)])


def _record(tr):
    found = tiny.cell()
    return serving.RunRecord(cell="c", cfg=found["config"],
                             traffic=found["traffic"], peaks=tiny.PEAKS,
                             window=serving.Window(t0=0.0, t1=1.0), trace=tr)


def test_idle_split_on_a_known_trace():
    # step 1: idle 0-0.5 and 1-2 before the program, 10-12 after it;
    # step 2: 12-15 before, 35-40 after; step 3 runs no decode program
    assert step_split.idle_split(_trace()) == [
        pytest.approx((1.5 * MS, 2 * MS)), pytest.approx((3 * MS, 5 * MS))]
    rec = _record(_trace())
    assert run.reader("engine_launch_idle_ms")(rec) == pytest.approx(2.25)
    assert run.reader("engine_readback_idle_ms")(rec) == pytest.approx(3.5)


def test_split_sums_to_the_host_time_of_decode_steps():
    tr = _trace()
    tr.spans = tr.spans[:2]                 # only the steps that decode
    rec = _record(tr)
    whole = run.reader("engine_host_ms")(rec)
    parts = sum(run.reader(name)(rec) for name in READERS)
    assert parts == pytest.approx(whole)


def test_a_program_that_outlasts_its_step_is_not_its_decode():
    tr = _trace()
    tr.modules[1] = devtrace.Event("jit__decode", 2 * MS, 13 * MS)
    assert step_split.idle_split(tr) == [pytest.approx((3 * MS, 5 * MS))]


@pytest.mark.parametrize("tr", [
    None, devtrace.Trace(spans=_trace().spans),
    devtrace.Trace(modules=_trace().modules, ops=_trace().ops)],
    ids=["untraced", "no-device-events", "no-step-spans"])
def test_readers_without_their_input_return_nothing(tr):
    for name in READERS:
        assert run.reader(name)(_record(tr)) is None, name


def test_each_bench_step_holds_the_engines_own_step_span(tmp_path):
    """On a CPU-recorded trace of the tiny cell: the benchmark's step span
    brackets exactly one ``serve.step`` of the program, with its decode
    phases inside, so the readers' steps are the engine's."""
    from jax.profiler import ProfileData

    found = tiny.cell()
    cfg = found["config"]
    arch = run.arch_of(cfg)
    eng = serving.build_engine(cfg, arch, SEED,
                               serving.act_step(cfg, arch, SEED))
    serving.warm_up(eng, cfg)
    serving.run_window(eng, cfg, found["traffic"], SEED, 2.0,
                       trace_dir=str(tmp_path), trace_s=1.0)
    serving.free(eng)
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = [(e.name, e.start_ns, e.end_ns)
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(("bench.step", "serve."))]
    bench = [s for s in spans if s[0] == "bench.step"]
    assert bench
    for _, lo, hi in bench:
        inner = [s[0] for s in spans if lo <= s[1] and s[2] <= hi]
        assert inner.count("serve.step") == 1
        assert "serve.admit" in inner
    assert {"serve.launch", "serve.readback", "serve.emit"} <= {
        s[0] for s in spans}
    # the CPU has no device planes: nothing to split, no number
    tr = devtrace.load(str(tmp_path))
    assert step_split.idle_split(tr) == []
