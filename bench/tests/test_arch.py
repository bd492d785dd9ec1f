"""Architecture modules: the dense module reproduces the harness's readings
from before the modules existed, and a new architecture is taken as new
files only.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (HERE, BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

import reference  # noqa: E402
import run  # noqa: E402
import serving  # noqa: E402
import tiny  # noqa: E402
import traffic_gen  # noqa: E402
import weights  # noqa: E402

SEED = 2 ** 31 + 977


# ------------------------------------------------------------- dense pins

# Readings of the harness as it was before the architecture modules (the
# dense decoder hard-wired into weights.py, reference.py and flops.py),
# recorded on the CPU: a digest of every program leaf, the activation step,
# digests of the reference's and the control's logits for one prompt, and
# the operation counts at the published and the tiny widths.  Four and
# five layers put layers both in scan groups and in the unscanned rest.
PINS = os.path.join(HERE, "dense_pins.json")
TINY = dict(d_model=64, n_heads=4, head_dim=16, d_ff=128, vocab_size=256)
CASES = {"opt-like": ("opt-1.3b", dict(TINY, n_layers=4, n_kv_heads=4)),
         "olmo-like": ("olmo-1b", dict(TINY, n_layers=5, n_kv_heads=2))}


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for name, a in arrays:
        a = np.asarray(a)
        h.update(f"{name}|{a.dtype}|{a.shape}|".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _readings(config: str, sizes: dict) -> dict:
    from repro.models.model_zoo import build_model
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    full = dict(cfg["model"])
    cfg["model"].update(sizes)
    m = cfg["model"]
    arch = run.arch_of(cfg)
    scale = serving.act_step(cfg, arch, SEED)
    model = build_model(serving.model_config(cfg, scale))
    params = weights.program_params(model, arch, m, SEED)
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    out = {"act_scale": scale, "params": _digest(
        (jax.tree_util.keystr(p), v) for p, v in leaves)}
    toks = traffic_gen.prompt_tokens(SEED, 0, 20, m["vocab_size"])
    dslot = cfg["dslot"] if serving.uses_dslot(cfg) else None
    for pre, control in (("", False), ("control_", True)):
        lg = reference.forward(arch, m, dslot, SEED, toks, 32, scale or 1.0,
                               6, control=control)
        out[pre + "logits"] = _digest([("l", lg)])
        out[pre + "logits_head"] = [float(v) for v in lg[-1, :3]]
    out["flops"] = {}
    for which, mm in (("full", full), ("tiny", m)):
        for c in (1, 1000):
            out["flops"][f"{which}.decode.{c}"] = \
                arch.decode_token_flops(mm, c)
        for c in (1, 200):
            out["flops"][f"{which}.prefill.{c}"] = arch.prefill_flops(mm, c)
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_module_matches_parent(case):
    with open(PINS) as f:
        want = json.load(f)[case]
    assert _readings(*CASES[case]) == want


# ------------------------------------------------------------- a new arch

# A configuration whose layers alternate the program's ``attn`` and ``moe``
# kinds, with an architecture module of its own: the dense module's
# attention, then either its MLP or a mixture of experts computed expert by
# expert (softmax router, top-k weights renormalized, as the program's
# layer).  Four experts, two per token, dropless at this size.
TINY_MOE_CONFIG = {
    "name": "tiny-moe", "source": "a test configuration", "arch": "tiny_moe",
    "model": {
        "name": "tiny-moe", "family": "moe", "n_layers": 4, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
        "vocab_size": 256, "qkv_bias": False, "rope_theta": 10000.0,
        "norm": "rmsnorm", "act": "silu", "glu": True,
        "tie_embeddings": False, "dtype": "float32", "scan_unroll": 1,
        "block_pattern": ["attn", "moe"], "n_experts": 4, "top_k": 2,
        "capacity_factor": 4.0},
    "dslot": {"enabled": False},
    "serve": {"n_slots": 4, "max_len": 96, "prefill_chunk": 16,
              "chunks_per_step": 2, "max_queue": None},
}

TINY_MOE_ARCH = '''
"""Attention + MLP and attention + mixture-of-experts layers."""
import jax
import jax.numpy as jnp

from arch import dense
from reference import int8_rows, mm
from weights import F32

global_spec, norm, embed, logits = (dense.global_spec, dense.norm,
                                    dense.embed, dense.logits)


def layer_kinds(m):
    pat = m["block_pattern"]
    return [pat[i % len(pat)] for i in range(m["n_layers"])]


def layer_spec(m, kind):
    spec = dense.layer_spec(m, "attn")
    if kind == "moe":
        spec = {k: v for k, v in spec.items() if not k.startswith("mlp.")}
        d, f, e, dt = m["d_model"], m["d_ff"], m["n_experts"], m["dtype"]
        spec.update({"moe.router": ((d, e), "matrix", F32),
                     "moe.up": ((e, d, f), "matrix", jnp.dtype(dt)),
                     "moe.gate": ((e, d, f), "matrix", jnp.dtype(dt)),
                     "moe.down": ((e, f, d), "matrix", jnp.dtype(dt))})
    return spec


def moe_layer(x, p, m, dslot, step, n_planes, control):
    w = dense.f32_leaves(p, control)
    if control:
        w = {k: int8_rows(v, 1) if v.ndim == 3 else v for k, v in w.items()}
    act = dense.row_rounding(control)
    x = dense.attention(x, w, m, control)
    h = act(norm(x, w, "norm2", m))
    probs = jax.nn.softmax(mm(h, w["moe.router"]), axis=-1)
    top, idx = jax.lax.top_k(probs, m["top_k"])
    top = top / jnp.sum(top, -1, keepdims=True)
    y = jnp.zeros_like(x)
    for e in range(m["n_experts"]):
        inner = jax.nn.silu(mm(h, w["moe.gate"][e])) * mm(h, w["moe.up"][e])
        share = jnp.sum(jnp.where(idx == e, top, 0.0), -1)
        y = y + share[:, None] * mm(act(inner), w["moe.down"][e])
    return x + y


LAYERS = {"attn": dense.LAYERS["attn"], "moe": moe_layer}


def _body(m):
    d, f, hd = m["d_model"], m["d_ff"], dense.head_dim(m)
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    kinds = layer_kinds(m)
    return (len(kinds) * attn + kinds.count("attn") * 3 * d * f
            + kinds.count("moe") * (d * m["n_experts"]
                                    + m["top_k"] * 3 * d * f))


def decode_token_flops(m, ctx):
    return 2.0 * (_body(m) + m["d_model"] * m["vocab_size"]) \\
        + dense.attention_flops(m, ctx)


def prefill_flops(m, n):
    pairs = n * (n + 1) / 2.0
    return 2.0 * _body(m) * n + dense.attention_flops(m, pairs) \\
        + 2.0 * m["d_model"] * m["vocab_size"]
'''


def test_new_arch_is_found_by_name_without_code_edits(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    for sub in ("traffic", "metrics"):          # what is there already
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    for sub in ("configs", "arch", "cells"):
        (bench / sub).mkdir()
    # the three files a configuration brings
    (bench / "configs" / "tiny-moe.json").write_text(
        json.dumps(TINY_MOE_CONFIG))
    (bench / "arch" / "tiny_moe.py").write_text(TINY_MOE_ARCH)
    (bench / "cells" / "tiny-moe.decode.json").write_text(json.dumps(
        {"check_tokens": 24, "limits": {"mean_logit_gap": 0.005}}))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["workloads"].append({"name": "tiny-moe.decode", "config": "tiny-moe",
                              "traffic": "decode", "chips": 1, "why": "x"})
    for m in spec["per_layer"]:
        if m["name"] == "step_mfu":     # reads the module's operation count
            m["workloads"].append("tiny-moe.decode")
    monkeypatch.setattr(run, "BENCH", str(bench))
    found = run.find_cell("tiny-moe.decode", spec)
    tiny.small_traffic(found["traffic"])
    arch = run.arch_of(found["config"])
    assert arch.layer_kinds(found["config"]["model"]) == [
        "attn", "moe", "attn", "moe"]
    out = run.run("tiny-moe.decode", SEED, 3.0, True,
                  device_check=tiny.no_chip, found=found,
                  log=lambda m: None, cache=False)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["step_mfu"]["value"] > 0

    with pytest.raises(run.Refused, match="no architecture module"):
        run.arch_of(dict(found["config"], arch="no_such_arch"))
    nameless = {k: v for k, v in found["config"].items() if k != "arch"}
    with pytest.raises(run.Refused, match="names no arch"):
        run.arch_of(nameless)
