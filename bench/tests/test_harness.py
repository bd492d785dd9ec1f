"""The benchmark harness on the CPU: traffic, counts, reducers, refusals,
lookup by name, and one tiny run end to end.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import os
import shutil
import sys
from collections import Counter
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import devtrace  # noqa: E402
import flops  # noqa: E402
import run  # noqa: E402
import serving  # noqa: E402
import tiny  # noqa: E402
import traffic_gen  # noqa: E402

SEED = 2 ** 31 + 977


def _mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


# ------------------------------------------------------------- traffic

def test_closed_stream_ramps_first_round_and_clips():
    mix = _mix("decode")
    a = traffic_gen.closed_stream(mix, SEED, 8)
    b = traffic_gen.closed_stream(mix, SEED, 8)
    c = traffic_gen.closed_stream(mix, SEED + 1, 8)
    first_a = [next(a) for _ in range(64)]
    first_b = [next(b) for _ in range(64)]
    first_c = [next(c) for _ in range(64)]
    assert [vars(s) for s in first_a] == [vars(s) for s in first_b]
    assert [vars(s) for s in first_a] != [vars(s) for s in first_c]
    for s in first_a:
        assert 32 <= s.prompt_len <= 256
        assert s.prompt_len + s.max_new <= 2048        # fits max_len
    # the first round is the same ramp for every seed
    ramp = [128 * (k + 1) for k in range(8)]
    assert [s.max_new for s in first_a[:8]] == ramp
    assert [s.max_new for s in first_c[:8]] == ramp
    assert [s.budget for s in first_c[:8]] == [8, 6, 4, 8, 6, 4, 8, 6]
    assert all(1024 <= s.max_new <= 1792 for s in first_a[8:])


def test_every_seed_draws_the_same_sizes():
    mix = dict(_mix("decode"), pool=300)
    key = lambda s: (s.prompt_len, s.max_new, s.budget)  # noqa: E731
    a = traffic_gen.closed_stream(mix, SEED, 8)
    c = traffic_gen.closed_stream(mix, SEED + 1, 8)
    sa = [next(a) for _ in range(300)][8:]
    sc = [next(c) for _ in range(300)][8:]
    pool = Counter(map(key, traffic_gen._pool(mix, 300)))
    assert Counter(map(key, sa)) <= pool and Counter(map(key, sc)) <= pool
    assert Counter(s.budget for s in traffic_gen._pool(mix, 300)) == \
        Counter({8: 100, 6: 100, 4: 100})
    # each block of one request per client holds the same sizes, in the
    # seed's order: the first round's prompts, and every later block
    for b in range(0, len(sa), 8):
        assert Counter(map(key, sa[b:b + 8])) == \
            Counter(map(key, sc[b:b + 8]))
    a0, c0 = traffic_gen.closed_stream(mix, SEED, 8), \
        traffic_gen.closed_stream(mix, SEED + 1, 8)
    pa = [next(a0).prompt_len for _ in range(8)]
    pc = [next(c0).prompt_len for _ in range(8)]
    assert sorted(pa) == sorted(pc) and pa != pc


def test_prompt_tokens_from_seed():
    a = traffic_gen.prompt_tokens(SEED, 3, 100, 50272)
    assert (a == traffic_gen.prompt_tokens(SEED, 3, 100, 50272)).all()
    assert not (a == traffic_gen.prompt_tokens(SEED + 1, 3, 100,
                                               50272)).all()
    assert a.min() >= 0 and a.max() < 50272 and a.dtype.name == "int32"


# ------------------------------------------------------------- counts

def test_up_proj_work_counts_dense_rows_unpadded():
    f, b = flops.up_proj_work(8, 2048, 8192)
    assert f == 2 * 8 * 2048 * 8192
    assert b == 2 * (2048 * 8192 + 8 * 2048 + 8 * 8192)
    f, b = flops.up_proj_work(256, 2048, 8192)
    assert f == 2 * 256 * 2048 * 8192
    t, bound = flops.least_time(*flops.up_proj_work(8, 2048, 8192),
                                tiny.PEAKS)
    assert bound == "memory" and t == pytest.approx(b8 := 2 * (
        2048 * 8192 + 8 * 2048 + 8 * 8192) / 819e9) and b8 > 0


def test_model_flops_per_token():
    with open(os.path.join(BENCH, "configs", "opt-1.3b.json")) as f:
        cfg = json.load(f)
    m, dense = cfg["model"], run.arch_of(cfg)
    body, head = dense.matmul_params(m)
    assert body == 24 * (4 * 2048 * 2048 + 2 * 2048 * 8192)
    assert head == 2048 * 50272
    assert dense.decode_token_flops(m, 1) == 2 * (body + head) \
        + 4 * 24 * 2048
    assert dense.prefill_flops(m, 2) == 2 * body * 2 + 4 * 24 * 3 * 2048 \
        + 2 * head


# ------------------------------------------------------------- reducers

def _trace():
    E = devtrace.Event
    ms = 1e6
    return devtrace.Trace(
        modules=[E("jit__decode", 0, 10 * ms), E("jit__extend_lanes", 20 * ms,
                                                 30 * ms)],
        ops=[E("%fusion.1 = f32[] fusion()", 0, 4 * ms),
             E("%dslot_matmul_pallas.3 = f32[] custom-call()", 4 * ms,
               10 * ms),
             E("%dslot_matmul_pallas.7 = f32[] custom-call()", 20 * ms,
               29 * ms),
             E("%fusion.2 = f32[] fusion(%dslot_matmul_pallas.7)", 29 * ms,
               30 * ms)],
        spans=[E("bench.step", 0, 12 * ms), E("bench.step", 12 * ms, 40 * ms)])


def _record(tr, cfg="opt-1.3b"):
    found = tiny.cell(cfg)
    found["config"]["model"].update(d_model=2048, d_ff=8192)
    found["config"]["serve"].update(n_slots=8, prefill_chunk=128,
                                    chunks_per_step=2)
    w = serving.Window(t0=0.0, t1=1.0)
    return serving.RunRecord(cell="c", cfg=found["config"],
                             traffic=found["traffic"], peaks=tiny.PEAKS,
                             window=w, trace=tr)


def test_idle_share_and_host_time_on_a_known_trace():
    rec = _record(_trace())
    # busy 0-10 and 20-30 ms of the 0-40 ms step window
    assert run.reader("device_idle_share")(rec) == pytest.approx(50.0)
    # step 1: 12 ms span, 10 busy; step 2: 28 ms span, 10 busy
    assert run.reader("engine_host_ms")(rec) == pytest.approx(10.0)
    assert run.reader("decode_device_ms")(rec) == pytest.approx(10.0)
    assert devtrace.busy_ns(rec.trace, 0, 40e6) == pytest.approx(20e6)


def test_kernel_roofline_on_a_known_trace():
    rec = _record(_trace())
    dec_t, _ = flops.least_time(*flops.up_proj_work(8, 2048, 8192),
                                tiny.PEAKS)
    ext_t, _ = flops.least_time(*flops.up_proj_work(256, 2048, 8192),
                                tiny.PEAKS)
    want = 100.0 * (dec_t + ext_t) / (6e-3 + 9e-3)
    assert run.reader("dslot_up_roofline")(rec) == pytest.approx(want)


def test_readers_without_their_input_return_nothing():
    rec = _record(None)
    for name in ("device_idle_share", "engine_host_ms", "decode_device_ms",
                 "dslot_up_roofline", "dslot_skipped_frac"):
        assert run.reader(name)(rec) is None, name
    bare = devtrace.Trace(spans=_trace().spans)
    assert run.reader("dslot_up_roofline")(_record(bare)) is None
    assert run.reader("device_idle_share")(_record(bare)) is None


def test_breakdown_names_gaps_by_host_span():
    tr = _trace()
    tr.spans.append(devtrace.Event("bench.submit", 11e6, 19e6))
    b = devtrace.breakdown(tr, 0, 40e6)
    assert b["device_ops"][0][0] == "dslot_matmul_pallas"
    assert b["idle_gaps"][0] == ["bench.submit", pytest.approx(0.010)]


# ------------------------------------------------------------- refusals

def _devices(monkeypatch, platform, kind, n=1):
    import jax
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev] * n)


def test_refuses_cpu_unknown_kind_and_too_few_chips(monkeypatch):
    _devices(monkeypatch, "cpu", "cpu")
    with pytest.raises(run.Refused, match="needs a TPU"):
        run.check_device(1)
    _devices(monkeypatch, "tpu", "TPU v9 imaginary")
    with pytest.raises(run.Refused, match="peaks.json"):
        run.check_device(1)
    _devices(monkeypatch, "tpu", "TPU v5 lite")
    with pytest.raises(run.Refused, match="4 chips"):
        run.check_device(4)
    dev, peaks = run.check_device(1)
    assert dev == {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert peaks["bf16_flops"] == 197e12


def test_command_refuses_without_a_tpu(capsys):
    assert run.main(["--workload", "opt-1.3b.decode", "--seed", "1",
                     "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""


# ------------------------------------------------------------- by name

def test_new_mix_is_found_by_name_without_code_edits(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    mix = dict(_mix("decode"), name="short")
    mix["output_tokens"] = {"dist": "uniform", "min": 64, "max": 256}
    (bench / "traffic" / "short.json").write_text(json.dumps(mix))
    (bench / "cells" / "opt-1.3b.short.json").write_text(json.dumps(
        {"check_tokens": 64, "limits": {"mean_logit_gap": 1.0}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "opt-1.3b.short", "config": "opt-1.3b",
                              "traffic": "short", "chips": 1, "why": "x"})
    monkeypatch.setattr(run, "BENCH", str(bench))
    found = run.find_cell("opt-1.3b.short", spec)
    assert found["traffic"]["output_tokens"]["max"] == 256
    assert found["numbers"]["check_tokens"] == 64
    assert {m["name"] for m in found["end_to_end"]} == {
        "output_tok_s", "itl_p95_ms", "setup_s"}
    specs = traffic_gen.closed_stream(found["traffic"], SEED, 16)
    later = [next(specs) for _ in range(48)][16:]
    assert all(64 <= s.max_new <= 256 for s in later)
    with pytest.raises(run.Refused):
        run.find_cell("opt-1.3b.nothing", spec)


# ------------------------------------------------------------- end to end

@pytest.mark.parametrize("config,dense,trace", [
    ("opt-1.3b", False, False), ("opt-1.3b", True, False),
    ("opt-1.3b", False, True), ("olmo-1b", False, False)])
def test_tiny_run_end_to_end(config, dense, trace):
    found = tiny.cell(config, limit=0.25, dense=dense)
    out = run.run(found["cell"]["name"], SEED, 3.0, trace,
                  device_check=tiny.no_chip, found=found, log=lambda m: None,
                  cache=False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"
    names = set(out["metrics"])
    if trace:
        assert {"step_mfu", "dslot_skipped_frac"} <= names
        assert "output_tok_s" not in names
        assert out["device"]["window_s"] > 0
        assert "breakdown" in out
    else:
        assert names == {"output_tok_s", "itl_p95_ms", "setup_s"}
        assert out["metrics"]["output_tok_s"]["value"] > 0
