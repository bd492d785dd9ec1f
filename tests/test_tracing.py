"""The engine's host spans and the model's named scopes.

``ServeEngine.step()`` records its phases as ``jax.profiler`` host spans
(``serve.*``), and the model puts ``attn``, ``kv_ring``, ``mlp``, ``mlp_up``
and ``logits`` into the HLO ``op_name`` of the ops they hold, so a profile
puts each device-idle gap down to a host phase and device time down to a
layer.  Here a tiny engine is traced on the CPU and its decode program's
compiled HLO is read.
"""

import dataclasses
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import DslotConfig
from repro.configs.registry import ARCHS
from repro.models.model_zoo import build_model
from repro.serve import Request, ServeConfig, ServeEngine

PHASES = ("serve.launch", "serve.readback", "serve.emit")


def _dense_cfg():
    # four layers: two groups of the pattern, so the layers run in a scan
    return dataclasses.replace(ARCHS["olmo-1b"].reduced(), n_layers=4)


def _dslot_cfg():
    return dataclasses.replace(
        _dense_cfg(), act="relu", glu=False,
        dslot=DslotConfig(enabled=True, block_m=16, block_n=32, block_k=16,
                          act_scale=0.05))


def _engine(cfg, **serve):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(5))
    return ServeEngine(model, params, ServeConfig(
        n_slots=2, max_len=48, prefill_chunk=8, chunks_per_step=2, **serve))


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, 256, n).astype(np.int32)


def _host_spans(trace_dir) -> list[dict]:
    """Every ``serve.*`` event of the trace's host planes."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no trace"
    spans = []
    for plane in ProfileData.from_file(files[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append({"name": e.name, "start": e.start_ns,
                                  "end": e.end_ns, "stats": dict(e.stats)})
    return spans


def _inside(inner: dict, outer: dict) -> bool:
    return outer["start"] <= inner["start"] and inner["end"] <= outer["end"]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A tiny dense engine, warmed up, then traced over one request's whole
    life: a two-chunk admission and its decode steps."""
    eng = _engine(_dense_cfg())
    eng.try_add(Request(uid=-1, prompt=_prompt(12, 1), max_new=2))
    eng.drain()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        req = Request(uid=42, prompt=_prompt(12), max_new=4)
        assert eng.try_add(req)
        eng.drain()
    finally:
        jax.profiler.stop_trace()
    return eng, _host_spans(d)


def test_every_serve_span_is_recorded(traced):
    eng, spans = traced
    names = {s["name"] for s in spans}
    assert names >= {"serve.step", "serve.admit", "serve.prefill",
                     "serve.merge", *PHASES}
    steps = [s for s in spans if s["name"] == "serve.step"]
    assert sorted(s["stats"]["step_num"] for s in steps) == list(
        range(eng.steps - len(steps) + 1, eng.steps + 1))


def test_decode_phases_nest_inside_their_step(traced):
    _, spans = traced
    steps = [s for s in spans if s["name"] == "serve.step"]
    for name in ("serve.admit", *PHASES):
        phase = [s for s in spans if s["name"] == name]
        assert phase
        for s in phase:
            assert sum(_inside(s, st) for st in steps) == 1, name
    # in order within a step: admit, launch, readback, emit
    for st in steps:
        inner = sorted((s for s in spans if s["name"] != "serve.step"
                        and _inside(s, st) and s["name"] in
                        ("serve.admit", *PHASES)), key=lambda s: s["start"])
        order = [s["name"] for s in inner]
        assert order in (["serve.admit"], ["serve.admit", *PHASES]), order


def test_admission_step_carries_prefill_and_merge_with_uid(traced):
    _, spans = traced
    merges = [s for s in spans if s["name"] == "serve.merge"]
    assert [int(s["stats"]["uid"]) for s in merges] == [42]
    step = next(s for s in spans
                if s["name"] == "serve.step" and _inside(merges[0], s))
    prefills = [s for s in spans
                if s["name"] == "serve.prefill" and _inside(s, step)]
    assert prefills
    assert all("42" in str(s["stats"]["uids"]).split(",") for s in prefills)
    # the merge is part of the admission phase of its step
    admit = next(s for s in spans
                 if s["name"] == "serve.admit" and _inside(s, step))
    assert _inside(merges[0], admit)
    assert all(_inside(p, admit) for p in prefills)


@pytest.mark.parametrize("cfg", [_dense_cfg, _dslot_cfg],
                         ids=["dense", "dslot"])
def test_decode_program_ops_carry_layer_scopes(cfg):
    eng = _engine(cfg())
    assert eng.model.decoder.n_groups > 1
    toks = jax.numpy.zeros((eng.n_slots, 1), jax.numpy.int32)
    text = eng._decode.lower(eng.params, eng.state, toks,
                             eng._budget_vector()).compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    scanned = [n for n in names if "/while/body/" in n]
    for path in ("/attn/", "/attn/kv_ring/", "/mlp/", "/mlp/mlp_up/"):
        # inside the layer scan's body, not only around it
        assert any(path in n for n in scanned), path
    assert any("/logits/" in n for n in names)
    # the ring's own ops are not counted as the MLP's, nor the reverse
    assert not any("/mlp/" in n for n in names if "/kv_ring/" in n)
