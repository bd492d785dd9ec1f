"""Tiny stand-ins for the cells, small enough for the CPU: the same files,
every width cut, so a test can drive ``run.run`` end to end."""

from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 17179869184}


def _load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def cell(config: str = "opt-1.3b", mix: str = "decode",
         limit: float = 1.0, dense: bool = False) -> dict:
    """``dense`` serves the configuration's widths on the dense path, with
    an OLMo-style SiLU+GLU block and no digit-serial up-projection."""
    cfg = _load("configs", f"{config}.json")
    cfg["model"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                        head_dim=16, d_ff=128, vocab_size=256)
    if dense:
        cfg["model"].update(act="silu", glu=True, norm="nonparam_ln",
                            qkv_bias=False)
        cfg["dslot"] = {"enabled": False}
    cfg["serve"].update(n_slots=4, max_len=96, prefill_chunk=16,
                        chunks_per_step=2)
    traffic = small_traffic(_load("traffic", f"{mix}.json"))
    name = f"{config}.{mix}"
    spec = _load("..", "BENCHMARK.json")
    return {"cell": {"name": name, "chips": 1},
            "config": cfg, "traffic": traffic,
            "numbers": {"check_tokens": 24,
                        "limits": {"mean_logit_gap": limit}},
            "end_to_end": [m for m in spec["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in spec["per_layer"]
                          if name in m.get("workloads", [name])]}


def small_traffic(traffic: dict) -> dict:
    """A mix's lengths cut to what a tiny engine holds (``max_len`` 96)."""
    traffic["prompt_tokens"].update(median=12, min=4, max=40)
    traffic["output_tokens"] = {"dist": "uniform", "min": 4, "max": 16}
    traffic["pool"] = 256
    return traffic


def no_chip(chips: int):
    return {"platform": "cpu", "kind": "cpu", "count": chips}, PEAKS
