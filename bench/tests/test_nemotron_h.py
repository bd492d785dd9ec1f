"""The Nemotron-H cell's own files (configuration, architecture module,
cell numbers, metric readers) drive ``run.run`` end to end on the CPU at a
tiny size and come out correct against the plain reference.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import run  # noqa: E402
import tiny  # noqa: E402

SEED = 2 ** 31 + 4099
CELL = "nemotron-3-nano-30b-a3b.decode"

# Every width cut, the structure kept: two periods of MEMEM*E, 16 experts
# routed top-6 with 4 held here, 8 SSM heads of 8 in 4 groups, a shared
# expert, no positions; float32, so the program meets the reference.
SIZES = dict(n_layers=14, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
             d_ff=32, vocab_size=256, n_experts=16, top_k=6, experts_held=4,
             expert_offset=4, moe_shared_ff=48, ssm_state=8, ssm_heads=8,
             ssm_headdim=8, ssm_groups=4, ssm_chunk=8, dtype="float32")


def test_tiny_nemotron_h_cell_is_correct():
    found = run.find_cell(CELL)
    m = found["config"]["model"]
    m.update(SIZES)
    found["config"]["serve"].update(n_slots=4, max_len=96, prefill_chunk=16,
                                    chunks_per_step=2)
    found["numbers"]["check_tokens"] = 24
    tiny.small_traffic(found["traffic"])
    arch = run.arch_of(found["config"])
    assert arch.layer_kinds(m)[:7] == ["ssm", "moe_only", "ssm", "moe_only",
                                       "ssm", "attn_only", "moe_only"]
    out = run.run(CELL, SEED, 3.0, True, device_check=tiny.no_chip,
                  found=found, log=lambda msg: None, cache=False)
    assert out["correct"] is True, out["checks"]
    assert out["checks"]["mean_logit_gap"]["limit"] > \
        out["checks"]["mean_logit_gap"]["value"]
    assert out["failed"] == 0 and out["attempted"] > 0
    # 4 slots, 4 held experts of 16, top 6: 6 * 4 / 16 = 1.5 of 4 rows
    assert 0 < out["metrics"]["moe_row_use"]["value"] <= 100


def test_operation_and_byte_counts_at_published_widths():
    """The counts the roofline reads, at the configuration's widths: the
    step moves the weights (held experts whole), the f32 SSM states twice
    and the live KV once; a token's operations count the held experts at
    their routed share."""
    found = run.find_cell(CELL)
    m = found["config"]["model"]
    arch = run.arch_of(found["config"])
    b = arch.decode_step_bytes(m, 128, 1000)
    ssm = 12 * 128 * 2 * 64 * 64 * 128 * 4
    kv = 4 * 128 * 1001 * 2 * 2 * 128 * 2
    assert 6.2e9 < b - ssm - kv < 6.4e9          # weights, head, tails, io
    assert 13.1e9 < b < 13.4e9
    f = arch.decode_token_flops
    assert f(m, 1000) - f(m, 0) == 4 * 4 * 1000 * 32 * 128
    more = dict(m, experts_held=32)              # twice the share routed here
    assert f(more, 0) - f(m, 0) == 12 * 2 * (6 * 16 / 128) * 2 * 2688 * 1856
    assert arch.prefill_flops(m, 1) == f(m, 1)   # one token, one key


def test_decode_roofline_reads_the_step_work_over_its_time():
    """Two traced decode programs of 20 ms, four tokens emitted in the
    traced span (two a step) at live contexts 11, 12, 21 and 22: the share
    is the least time of two rows at context 16.5 over 20 ms; a parent's
    module without ``decode_step_bytes`` reads nothing."""
    import types

    import devtrace
    import flops
    import serving

    found = run.find_cell(CELL)
    arch = run.arch_of(found["config"])
    MS = 1e6
    E = devtrace.Event
    tr = devtrace.Trace(
        modules=[E("jit__decode", 1 * MS, 21 * MS),
                 E("jit__decode", 31 * MS, 51 * MS)],
        spans=[E("bench.step", 0, 30 * MS), E("bench.step", 30 * MS, 60 * MS)])
    w = serving.Window(t0=0.0, t1=10.0, trace_t=(5.0, 6.0))
    w.reqs = {0: types.SimpleNamespace(prompt=[0] * 10),
              1: types.SimpleNamespace(prompt=[0] * 20)}
    w.tok_t = {0: [5.1, 5.2], 1: [5.1, 5.2, 7.0]}
    rec = serving.RunRecord(cell=CELL, cfg=found["config"], traffic={},
                            peaks=tiny.PEAKS, window=w, trace=tr, arch=arch)
    read = run.reader("decode_roofline")
    m = found["config"]["model"]
    least, bound = flops.least_time(2 * arch.decode_token_flops(m, 16.5),
                                    arch.decode_step_bytes(m, 2, 16.5),
                                    tiny.PEAKS)
    assert bound == "memory"
    assert abs(read(rec) - 100 * least / 20e-3) < 1e-9
    rec.arch = types.SimpleNamespace(decode_token_flops=arch.decode_token_flops)
    assert read(rec) is None
