"""Pin the public API surface and the deprecation shims.

The serving redesign froze the construction/result contract:
``ServeEngine(model, params, cfg: ServeConfig)`` and ``GenerateResult``
from both generation paths.  These tests pin the exported names and the
load-bearing signatures so an accidental rename or a dropped shim fails
tier-1 instead of breaking downstream callers silently.
"""

import inspect
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.kernels
import repro.runtime
import repro.serve
from repro.serve import GenerateResult, Request, ServeConfig, ServeEngine
from repro.serve import engine as engine_mod
from repro.serve import generate

SERVE_ALL = {
    "ServeConfig", "Request", "ServeEngine", "generate", "GenerateResult",
    "PrefillPipeline", "PrefillTask",
    "PENDING", "PREFILLING", "DECODING", "DONE", "CANCELLED",
    "TIMEOUT", "QUARANTINED", "FAILED",
    "Fault", "FaultPlan", "FaultInjector", "TransientFault", "FAULT_KINDS",
    "InvariantViolation", "audit_engine", "check_invariants",
    "SloConfig", "SloController", "SloSignals", "TierSpec", "default_tiers",
    "RESERVED", "STANDARD", "DEGRADABLE", "TIERS",
}

RUNTIME_ALL = {
    "AdaptiveBudget", "Fixed", "PerLayerSchedule", "PolicyFeedback",
    "PrecisionPolicy", "current_precision", "precision_scope",
}

KERNELS_ALL = {
    "DslotMatmulOut", "DslotStats", "DslotWeights", "dslot_matmul",
    "dslot_prepare", "dslot_execute", "calibrate_scale",
    "prepare_call_count", "dslot_matmul_pallas",
    "dslot_matmul_pallas_batched", "colsum_tables", "select_block_k",
    "q_storage_dtype", "quantize_activations", "dslot_matmul_ref",
    "csd_matmul_ref", "make_planes", "sd_digit_plane",
}


def test_exported_surface_pinned():
    assert set(repro.serve.__all__) == SERVE_ALL
    assert set(repro.runtime.__all__) == RUNTIME_ALL
    assert set(repro.kernels.__all__) == KERNELS_ALL
    for mod in (repro.serve, repro.runtime, repro.kernels):
        for name in mod.__all__:
            assert hasattr(mod, name), f"{mod.__name__}.{name} missing"


def test_serve_engine_signature_pinned():
    sig = inspect.signature(ServeEngine.__init__)
    names = list(sig.parameters)
    # the blessed surface: (model, params, cfg) — everything after is the
    # keyword-only deprecation shim
    assert names[:4] == ["self", "model", "params", "cfg"]
    assert sig.parameters["cfg"].default is None
    legacy = {n for n, p in sig.parameters.items()
              if p.kind is inspect.Parameter.KEYWORD_ONLY}
    assert legacy == {"n_slots", "max_len", "sample", "precision_policy",
                      "serve_config"}


def test_generate_signature_pinned():
    sig = inspect.signature(generate)
    names = list(sig.parameters)
    assert names == ["model", "params", "batch", "max_new_tokens",
                     "max_len", "sample", "key", "n_planes", "return_stats"]
    # precision is named n_planes on every public surface
    assert "n_planes" in inspect.signature(
        repro.runtime.precision_scope).parameters
    assert "n_planes" in {f.name for f in Request.__dataclass_fields__.values()}
    assert "n_planes" in {
        f.name for f in GenerateResult.__dataclass_fields__.values()}


def test_serve_config_fields_pinned():
    assert {f.name for f in ServeConfig.__dataclass_fields__.values()} == {
        "n_slots", "max_len", "prefill_chunk", "chunks_per_step",
        "max_queue", "jit_prefill", "sample", "precision_policy", "slo",
        "mesh", "tp_axis",
        "default_deadline_steps", "max_step_retries",
        "quarantine_nonfinite", "faults"}
    assert ServeConfig().mesh is None and ServeConfig().tp_axis == "model"
    # hardening defaults: no deadline, quarantine ON, no fault plan
    cfg = ServeConfig()
    assert cfg.default_deadline_steps is None
    assert cfg.max_step_retries == 2
    assert cfg.quarantine_nonfinite is True
    assert cfg.faults is None


def test_hardening_surface_pinned():
    """The PR 9 failure surface: deadlines, fault plane, shutdown, and the
    terminal phase strings downstream dashboards key on."""
    from repro.serve import (FAILED, FAULT_KINDS, Fault, FaultPlan,
                             QUARANTINED, TIMEOUT)

    assert "deadline_steps" in {
        f.name for f in Request.__dataclass_fields__.values()}
    assert Request.__dataclass_fields__["deadline_steps"].default is None

    # phase strings are wire format — pin the values, not just the names
    assert TIMEOUT == "timeout"
    assert QUARANTINED == "quarantined"
    assert FAILED == "failed"
    assert set(FAULT_KINDS) == {
        "nan_logits", "inf_logits", "kv_corrupt", "lane_exception",
        "admission_exception", "decode_exception", "cancel", "slow_step"}

    # Fault/FaultPlan are declarative data
    assert {f.name for f in Fault.__dataclass_fields__.values()} == {
        "kind", "step", "slot", "uid", "count", "value"}
    assert {f.name for f in FaultPlan.__dataclass_fields__.values()} == {
        "faults", "seed"}
    rnd = inspect.signature(FaultPlan.random).parameters
    assert {"n_faults", "max_step", "n_slots", "uids", "kinds"} <= set(rnd)

    # shutdown + audit surface
    drain = inspect.signature(ServeEngine.drain).parameters
    assert list(drain) == ["self", "max_steps"]
    assert drain["max_steps"].default is None
    assert list(inspect.signature(ServeEngine.close).parameters) == ["self"]
    assert isinstance(ServeEngine.closed, property)
    assert callable(ServeEngine.check_invariants)


def test_sharding_surface_pinned():
    # the tensor-parallel surface: mesh/tp_axis keywords on the prepare
    # entry points, the shard-count property, and the EP budget keywords —
    # all keyword-only / defaulted so single-device callers never change.
    from repro.distributed.expert_parallel import apply_moe_ep
    from repro.kernels.ops import DslotWeights, dslot_prepare
    from repro.models.model_zoo import Model

    prep = inspect.signature(dslot_prepare).parameters
    assert {"mesh", "tp_axis"} <= set(prep)
    assert prep["mesh"].default is None
    assert prep["tp_axis"].default == "model"
    assert prep["mesh"].kind is inspect.Parameter.KEYWORD_ONLY

    pd = inspect.signature(Model.prepare_dslot).parameters
    assert list(pd) == ["self", "params", "mesh", "tp_axis"]
    assert pd["mesh"].default is None

    ep = inspect.signature(apply_moe_ep).parameters
    assert {"expert_planes", "n_bits"} <= set(ep)
    assert ep["expert_planes"].default is None and ep["n_bits"].default == 8

    assert {"mesh", "tp_axis"} <= set(
        f.name for f in DslotWeights.__dataclass_fields__.values())
    assert DslotWeights.tp_shards.fget is not None      # property exists


def test_generate_result_fields_pinned():
    assert {f.name for f in GenerateResult.__dataclass_fields__.values()} == {
        "tokens", "n_planes", "planes_used_mean", "skipped_frac",
        "planes_bounded_mean", "expert_rows", "ttft_steps", "steps", "phase",
        "uid", "tier"}


# ------------------------------------------------------- deprecation shims

@pytest.fixture(scope="module")
def lm():
    from repro.configs.registry import ARCHS
    from repro.models.model_zoo import build_model

    cfg = ARCHS["olmo-1b"].reduced()
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def test_legacy_engine_kwargs_shim_warns_once(lm):
    model, params = lm
    engine_mod._LEGACY_WARNED.discard("ServeEngine.kwargs")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        eng = ServeEngine(model, params, n_slots=2, max_len=32)
        ServeEngine(model, params, serve_config=ServeConfig(
            n_slots=1, max_len=32))
    assert len([w for w in rec
                if issubclass(w.category, DeprecationWarning)]) == 1
    # the shim maps onto a real config — behaviour, not just acceptance
    assert eng.cfg.n_slots == 2 and eng.cfg.max_len == 32
    assert eng.serve_config is eng.cfg        # back-compat alias
    r = Request(uid=1, prompt=np.asarray([1, 2, 3], np.int32), max_new=2)
    assert eng.try_add(r)
    while not r.done:
        eng.step()
    assert len(r.out) == 2 and r.result.phase == "done"


def test_mixing_cfg_and_legacy_kwargs_rejected(lm):
    model, params = lm
    with pytest.raises(TypeError, match="not both"):
        ServeEngine(model, params, ServeConfig(), n_slots=2)


def test_generate_return_stats_shim(lm):
    model, params = lm
    batch = {"tokens": jnp.asarray([[1, 2, 3]], jnp.int32)}
    engine_mod._LEGACY_WARNED.discard("generate.return_stats")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        toks, stats = generate(model, params, batch, 2, return_stats=True)
        bare = generate(model, params, batch, 2, return_stats=False)
    assert len([w for w in rec
                if issubclass(w.category, DeprecationWarning)]) == 1
    assert toks.shape == (1, 2) and stats == {}       # non-DSLOT: empty
    assert bare.shape == (1, 2)
    res = generate(model, params, batch, 2)
    assert isinstance(res, GenerateResult)
    np.testing.assert_array_equal(np.asarray(res.tokens), np.asarray(toks))
