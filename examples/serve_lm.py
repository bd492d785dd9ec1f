"""Serving example: slot-pool continuous batching + DSLOT digit-serial MLPs
+ SLO-driven precision elasticity.

Serves the seamless-m4t backbone (the assigned arch whose ReLU FFN admits
full DSLOT early-negative-termination) in reduced form through the batch
``generate`` API, then drives the slot-pool ``ServeEngine`` — streaming
tokens as they land, and shedding digit planes per QoS tier when an
admission burst overloads the pool.

    PYTHONPATH=src python examples/serve_lm.py
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import DslotConfig
from repro.configs.registry import get_arch
from repro.models import stats
from repro.models.model_zoo import build_model
from repro.serve import (DEGRADABLE, RESERVED, STANDARD, Request,
                         ServeConfig, ServeEngine, SloConfig, generate)


def main():
    cfg = get_arch("seamless-m4t-medium").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(1)

    batch = {
        "tokens": jax.random.randint(key, (4, 12), 0, cfg.vocab_size),
        "src_embeds": jax.random.normal(key, (4, 8, cfg.d_model)) * 0.02,
    }
    res = generate(model, params, batch, 8)
    print("enc-dec batched generation:", res.tokens.shape)

    # ---- DSLOT digit-serial MLPs (ReLU FFN -> early termination applies),
    # at the 128x128 blocks the compiled TPU kernel takes
    dcfg = dataclasses.replace(cfg, dslot=DslotConfig(
        enabled=True, n_planes=8))
    dmodel = build_model(dcfg)
    dparams = dmodel.prepare_dslot(params)      # weight-stationary lowering,
    res2 = generate(dmodel, dparams, batch, 8)  # done once for all requests
    same = bool(jnp.mean((res.tokens == res2.tokens)
                         .astype(jnp.float32)) > 0.9)
    print("dslot-mode generation agrees with dense:", same)
    # per-request runtime precision + planes-executed accounting, all on
    # the one GenerateResult
    res3 = generate(dmodel, dparams, batch, 8,
                    n_planes=jnp.asarray([8, 8, 4, 2], jnp.int32))
    if res3.planes_used_mean is not None:
        used = np.asarray(res3.planes_used_mean)
        skip = np.asarray(res3.skipped_frac)
        for i in range(used.shape[0]):
            print(f"  request {i}: planes/row {used[i]:.2f}, "
                  f"skipped {skip[i]:.1%}")
    # eager forward statistics through the (scan-safe) stats side channel
    with stats.collect() as sink:
        dmodel.forward(dparams, batch)
    vals = [float(jnp.mean(v)) for v in jax.device_get(
        sink.get("mlp_dslot_skipped_frac", []))]
    if vals:
        print(f"digit-serial MLP calls: {len(vals)}, mean skipped MXU "
              f"passes {np.mean(vals):.1%}")

    # ---- slot-pool continuous batching with batched chunked admission
    # try_add only enqueues; each engine step interleaves ONE batched
    # admission forward — up to chunks_per_step PREFILLING prompts advance
    # together, one prefill_chunk each, at ragged per-request offsets — so
    # long prompts trickle in without stalling live slots for a full
    # forward, and bursts drain two prompts at a time (watch two slots sit
    # in 'prefilling' simultaneously below).
    lcfg = get_arch("olmo-1b").reduced()
    lmodel = build_model(lcfg)
    lparams = lmodel.init(jax.random.PRNGKey(2))
    eng = ServeEngine(lmodel, lparams, ServeConfig(
        n_slots=2, max_len=48, prefill_chunk=4, chunks_per_step=2))
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, lcfg.vocab_size,
                                        size=3 + 4 * i).astype(np.int32),
                    max_new=3 + i) for i in range(4)]
    # streaming, push form: uid 0 reports every token the step it lands
    reqs[0].on_token = lambda req, tok, step: print(
        f"    uid {req.uid} token {tok} @ step {step}")
    for r in reqs:
        eng.try_add(r)                   # non-blocking: queued, FIFO
    finished = []
    while len(finished) < len(reqs):
        finished += eng.step()
        print(f"  step {eng.steps:2d}: slots={eng.slot_phases()} "
              f"queued={eng.queue_depth}")
    print("continuous batching: served", len(finished), "requests;",
          {r.uid: (len(r.out), f"ttft={r.result.ttft_steps} steps")
           for r in finished})
    # streaming, pull form: a generator handle drives the engine itself
    tail = Request(uid=99, prompt=rng.integers(
        0, lcfg.vocab_size, size=6).astype(np.int32), max_new=4)
    print("  streamed:", list(eng.stream(tail)), "ttft =",
          tail.result.ttft_steps, "steps")

    # ---- SLO-aware precision elasticity: QoS tiers under an overload burst
    # A calibrated DSLOT model (fixed act_scale -> chunk-invariant
    # quantization) serves a 4x burst; the SloController sheds degradable
    # tiers' digit planes to hold latency, never touches reserved's floor,
    # and restores the planes once the queue drains.
    scfg = dataclasses.replace(
        lcfg, act="relu", glu=False,
        dslot=DslotConfig(enabled=True, act_scale=0.05))
    smodel = build_model(scfg)
    sparams = smodel.init(jax.random.PRNGKey(3))
    eng2 = ServeEngine(smodel, sparams, ServeConfig(
        n_slots=2, max_len=48, prefill_chunk=4, chunks_per_step=2,
        slo=SloConfig(queue_high_water=2, shed_patience=2,
                      restore_patience=2, target_ttft_steps=8)))
    tiers = [RESERVED, STANDARD] + [DEGRADABLE] * 6
    burst = [Request(uid=i, tier=t,
                     prompt=rng.integers(0, scfg.vocab_size,
                                         size=8).astype(np.int32),
                     max_new=4)
             for i, t in enumerate(tiers)]
    for r in burst:
        eng2.try_add(r)
    while not all(r.done for r in burst):
        eng2.step()
    for tier in (RESERVED, STANDARD, DEGRADABLE):
        rs = [r.result for r in burst if r.tier == tier]
        print(f"  {tier:10s} planes/row "
              f"{np.mean([r.planes_used_mean for r in rs]):.2f}  "
              f"ttft p95 {np.percentile([r.ttft_steps for r in rs], 95):.0f}"
              f" steps  [{len(rs)} reqs]")
    print("  controller:", eng2.slo.summary())


if __name__ == "__main__":
    main()
