"""One run of one benchmark cell on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name from ``BENCHMARK.json``: the
configuration in ``bench/configs/<config>.json`` and the architecture
module it names in ``bench/arch/<arch>.py``, the traffic mix in
``bench/traffic/<mix>.json``, the cell's own numbers (the tokens checked
and the correctness limit) in ``bench/cells/<cell>.json``, and each per-layer
metric's reader in ``bench/metrics/<metric>.py``.

The run refuses, with no result line, a platform other than TPU, fewer
chips than the cell asks for, and a ``device_kind`` that ``bench/peaks.json``
does not list.  It builds the weights on the device from the seed, warms up
the cell's shapes (all of this is ``setup_s``), measures for ``--seconds``,
then checks a sample of the finished requests against the plain reference
(``bench/reference.py``).  The last line of stdout is one JSON object;
``--trace 1`` reports the per-layer metrics read from a profiler trace of
the window's last seconds in place of the end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


class Refused(RuntimeError):
    """The run cannot measure here; exit non-zero with no result."""


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find_cell(name: str, spec: dict | None = None) -> dict:
    """The cell's entry and everything it names, read by name."""
    spec = spec or load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    return {
        "cell": w,
        "config": load_json(BENCH, "configs", f"{w['config']}.json"),
        "traffic": load_json(BENCH, "traffic", f"{w['traffic']}.json"),
        "numbers": load_json(BENCH, "cells", f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def check_device(chips: int, peaks: dict | None = None) -> tuple[dict, dict]:
    """(device description, its peaks); refuses anything but enough TPUs
    listed in the peaks table."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"needs a TPU, JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"cell needs {chips} chips, JAX found {len(devs)}")
    peaks = peaks if peaks is not None else load_json(BENCH, "peaks.json")
    kind = devs[0].device_kind
    if kind not in peaks:
        raise Refused(f"device_kind {kind!r} is not in bench/peaks.json")
    return ({"platform": devs[0].platform, "kind": kind,
             "count": chips}, peaks[kind])


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arch_of(cfg: dict):
    """The architecture module that the configuration names by its
    ``arch`` key, ``bench/arch/<arch>.py``; there is no default."""
    name = cfg.get("arch")
    if not name:
        raise Refused(f"configuration {cfg.get('name')!r} names no arch")
    path = os.path.join(BENCH, "arch", f"{name}.py")
    if not os.path.isfile(path):
        raise Refused(f"no architecture module {path}")
    return _module(path, f"bench_arch_{name}")


@functools.lru_cache(maxsize=None)
def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def memory_peak() -> int:
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        device_check=check_device, found: dict | None = None,
        log=print, cache: bool = True) -> dict:
    """One measured run; returns the result object (the last stdout line).

    ``device_check``, ``found`` and ``cache`` let a test drive the same
    path on the CPU with a tiny cell of its own, leaving JAX's cache
    settings as it found them."""
    import devtrace
    import serving

    found = found or find_cell(cell_name)
    cfg, traffic, nums = found["config"], found["traffic"], found["numbers"]
    arch = arch_of(cfg)
    device, peaks = device_check(found["cell"]["chips"])
    import jax
    log(f"device {device} jax {jax.__version__} cache "
        f"{enable_cache() if cache else 'off'}")

    scale = serving.act_step(cfg, arch, seed)
    eng = serving.build_engine(cfg, arch, seed, scale)
    serving.warm_up(eng, cfg)
    jax.block_until_ready(eng.state)
    setup_s = time.perf_counter() - T_START

    compiles = _count_compiles()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        w = serving.run_window(eng, cfg, traffic, seed, seconds,
                               settle_tokens=nums["check_tokens"],
                               trace_dir=tdir)
        n_compiles = compiles()
        device["memory_peak_bytes"] = memory_peak()
        e2e = serving.end_to_end(w)
        n_failed = serving.failed(w)
        results = serving.results_of(w)
        errors = list(eng.errors)
        serving.free(eng)
        tr = devtrace.load(tdir) if trace else None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    log(f"window {seconds}s: {len(w.reqs)} requests sent, {e2e['tokens']} "
        f"tokens, {len(w.steps)} steps (settling included), {e2e['gaps']} "
        f"gaps; compilations in window {n_compiles}; engine errors "
        f"{len(errors)}")
    log("inter-token gap percentiles (ms) p50/p90/p95/p99: " + " ".join(
        f"{v * 1e3:.2f}" for v in serving.percentiles(w, (50, 90, 95, 99))))
    log("steps in the window (ms): " + serving.step_summary(w))

    t_ref = time.perf_counter()
    ref = serving.check(cfg, arch, traffic, seed, w, nums["check_tokens"],
                        scale)
    log(f"reference over {ref['requests']} requests, {ref['tokens']} served "
        f"tokens, top-1 agreement {ref['top1_agreement']:.4f}, widest gap "
        f"{ref['max_logit_gap']:.4f}, {time.perf_counter() - t_ref:.1f}s")
    log("mean gap by plane budget: " + ", ".join(
        f"{k}: {b['mean_logit_gap']:.5f} over {b['tokens']}"
        for k, b in ref["by_planes"].items()))

    correct, checks = verdict(ref, n_failed, nums)

    metrics = {}
    if not trace:
        vals = {**e2e, "setup_s": setup_s}
        for m in found["end_to_end"]:
            metrics[m["name"]] = {"value": vals[m["name"]], "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(w.reqs),
           "failed": n_failed, "metrics": metrics, "device": device}
    if trace:
        rec = serving.RunRecord(cell=cell_name, cfg=cfg, traffic=traffic,
                                peaks=peaks, window=w, trace=tr,
                                results=results, arch=arch)
        for m in found["per_layer"]:
            v = reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = tr.window()
        device["busy_s"] = devtrace.busy_ns(tr, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        out["breakdown"] = devtrace.breakdown(tr, lo, hi)
    out["checks"] = checks
    return out


def verdict(ref: dict, n_failed: int, nums: dict) -> tuple[bool, dict]:
    """``correct``, and each number compared beside its limit: the mean
    logit gap of the checked tokens, that enough tokens were checked, and
    that no request failed."""
    checks = {
        "mean_logit_gap": {"value": ref["mean_logit_gap"],
                           "limit": nums["limits"]["mean_logit_gap"]},
        "checked_tokens": {"value": ref["tokens"],
                           "limit": nums["check_tokens"]},
        "failed_requests": {"value": n_failed, "limit": 0},
    }
    correct = (ref["tokens"] >= nums["check_tokens"]
               and ref["mean_logit_gap"] <= checks["mean_logit_gap"]["limit"]
               and n_failed == 0)
    return correct, checks


def _count_compiles():
    """Counter of programs compiled from now on: backend compilations and
    loads from the persistent cache (which the former leave out)."""
    import jax
    n = [0]

    def on_duration(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            n[0] += 1

    def on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            n[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return lambda: n[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log = lambda msg: print(f"[bench] {msg}", file=sys.stderr, flush=True)  # noqa: E731
    try:
        import repro.serve  # noqa: F401  the system under test
    except ImportError as e:
        log(f"cannot import the program: {e}")
        return 2
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  log=log)
    except Refused as e:
        log(f"refused: {e}")
        return 1
    for name, c in out["checks"].items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
