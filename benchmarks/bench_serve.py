"""Serving-layer benchmark: time-to-first-token and decode-stall under
staggered admissions, with and without the chunked-prefill pipeline.

What it measures (all wall-clock, host-synchronized — ``ServeEngine.step``
device-gets the sampled tokens, so ``perf_counter`` around it is honest):

* ``prefill_full_ms`` — one full-prompt prefill forward.  This is exactly
  what the pre-pipeline blocking ``try_add`` cost every live slot per
  admission.
* ``decode_step_ms`` — steady-state pooled decode step, no admission work.
* ``step_admission_ms`` — a decode step with one chunk of admission work
  riding along (median over a long prompt's prefill steps).
* ``decode_stall_ms = step_admission_ms - decode_step_ms`` — what an
  admission now costs the live slots per step.  The acceptance bar is
  ``decode_stall_ms < prefill_full_ms`` strictly: chunked admission must
  beat parking the pool for a whole prompt.
* per-request TTFT (steps and ms) under a staggered admission schedule.
* BURST admission (``"burst"`` key): N prompts enqueued at once, drained
  sequentially (``chunks_per_step=1``) vs batched (``chunks_per_step>1``,
  co-batched admission lanes).  Reports TTFT p50/p95 (ms and engine steps)
  and the total decode-stall of draining the burst.  The acceptance bar is
  the STEPS-domain form of "batched <= sequential stall", which is
  deterministic: every admission step stalls the pool exactly once, and
  batched admission must stall the pool on no more steps — and reach every
  request's first token in no more steps — than the sequential drain
  (expected: K-fold fewer with K lanes).  Wall-clock stall totals are
  reported alongside but NOT gated: at smoke scale a chunk forward is
  ~1-4 ms, so the ms-domain difference of two drains is timer-noise-bound
  on shared CI runners (the per-step cost bound is already gated by
  ``decode_stall_ms < prefill_full_ms`` above).

* ZOO-STACK bursts (``"burst_swa"`` / ``"burst_ssm"`` keys, PR 10): the
  same sequential-vs-batched burst drain on a sliding-window-attention
  stack (``h2o-danube-3-4b`` reduced) and a recurrent SSM stack
  (``mamba2-780m`` reduced).  Batched admission is no longer an
  attention-only fast path — every zoo stack rides the lanes — so each of
  these carries the same deterministic steps-domain gate
  (``batched_stall_leq_sequential``) as the primary burst.

* OVERLOAD (``"overload"`` key): the SLO control loop under a 4x burst.  A
  calibrated DSLOT model serves ``4 * n_slots`` requests enqueued at once,
  tiers cycling reserved/standard/degradable, with ``ServeConfig.slo`` set.
  Reports the accuracy-vs-latency Pareto sweep per QoS tier — mean planes
  actually executed (the accuracy/energy side) against p95 TTFT in ENGINE
  STEPS (the deterministic latency domain) — plus the weight-side
  ``mean_planes_bounded`` (digit planes never issued because of the static
  MSR bound baked into the prepared weights; request-independent, so it
  compounds with per-tier shedding) and the controller account
  (shed/restore events, minimum levels).  Gated (steps domain, so CI-safe):
  p95 TTFT stays within the analytic drain bound, the degradable tier's
  mean planes degrades below full precision (shedding did real work),
  reserved slots NEVER decode below their plane floor, and every tier's
  level is restored to its ceiling after the queue drains.

* CHAOS (``"chaos"`` key, PR 9): the hardened engine under a deterministic
  ``FaultPlan`` — a burst with an injected NaN (quarantine), a plan-driven
  cancel storm, and a transient lane failure, all in one run.  Gated
  (bit-exact / steps domain): no crash, invariants hold after EVERY tick,
  exactly the poisoned request quarantined, survivors' token streams
  bit-identical to the same run with no fault plan, recovery within an
  analytic bound of the fault-free drain.  ``--chaos-only`` runs just this
  scenario (the CI chaos lane), adding a ``"chaos_mesh"`` mirror on a
  2-shard tensor-parallel engine when >= 2 devices are visible.

Emits ``BENCH_serve.json``.  CPU numbers from the tiny reduced config are a
scheduling proxy, not TPU performance; the *ratios* (stall vs full prefill,
batched vs sequential burst) are the contract.

Standalone CLI (used by the CI smoke job):
    python benchmarks/bench_serve.py [--smoke] [--json BENCH_serve.json]
        [--prompt-len N] [--chunk N] [--slots N] [--burst N]
        [--burst-lanes N] [--chaos-only]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.models.model_zoo import build_model
from repro.serve import (DEGRADABLE, RESERVED, STANDARD, Request,
                         ServeConfig, ServeEngine, SloConfig)


def _mk_prompt(rng, n, vocab):
    return rng.integers(0, vocab, size=n).astype(np.int32)


def _timed_step(eng):
    t0 = time.perf_counter()
    done = eng.step()
    return (time.perf_counter() - t0) * 1e3, done


def run(model, params, cfg, prompt_len: int, chunk: int, n_slots: int,
        max_new: int, smoke: bool) -> dict:
    rng = np.random.default_rng(0)
    max_len = prompt_len + max_new + 8

    # ---- baseline: one full-prompt prefill forward (the blocking cost)
    full = {"tokens": jnp.asarray(_mk_prompt(rng, prompt_len,
                                             cfg.vocab_size)[None])}
    model.prefill(params, full, max_len=max_len)[0].block_until_ready()
    reps = 2 if smoke else 5
    t0 = time.perf_counter()
    for _ in range(reps):
        model.prefill(params, full, max_len=max_len)[0].block_until_ready()
    prefill_full_ms = (time.perf_counter() - t0) / reps * 1e3

    # ---- engine with live decoding slots
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=n_slots, max_len=max_len, prefill_chunk=chunk))
    live = [Request(uid=100 + i,
                    prompt=_mk_prompt(rng, chunk, cfg.vocab_size),
                    max_new=max_len - chunk - 1)
            for i in range(n_slots - 1)]
    for r in live:
        eng.try_add(r)
    # warmup: admissions trace the chunk/extend/decode shapes once
    warm = Request(uid=0, prompt=_mk_prompt(rng, prompt_len, cfg.vocab_size),
                   max_new=1)
    eng.try_add(warm)
    while not warm.done:
        eng.step()

    # steady-state decode, no admission in flight
    plain = [_timed_step(eng)[0] for _ in range(3 if smoke else 10)]
    decode_step_ms = statistics.median(plain)

    # ---- staggered chunked admissions: step times while prefill in flight
    admit_times, ttft = [], []
    n_admissions = 2 if smoke else 4
    for a in range(n_admissions):
        req = Request(uid=a + 1,
                      prompt=_mk_prompt(rng, prompt_len, cfg.vocab_size),
                      max_new=max_new)
        t_enq = time.perf_counter()
        if not eng.try_add(req):
            raise RuntimeError(f"admission queue rejected uid {req.uid}")
        while req.phase in ("pending", "prefilling"):
            ms, _ = _timed_step(eng)
            # only steps that actually carried admission work count toward
            # the stall metric — a step spent waiting for a free slot
            # (phase still "pending" afterwards) ran zero chunks and would
            # deflate the median toward the plain decode time
            if req.phase != "pending":
                admit_times.append(ms)
        ttft_ms = (time.perf_counter() - t_enq) * 1e3
        ttft.append({"uid": req.uid, "prompt_len": prompt_len,
                     "ttft_steps": req.ttft_steps, "ttft_ms": ttft_ms})
        for _ in range(2):                       # let the pool breathe
            eng.step()

    step_admission_ms = statistics.median(admit_times)
    decode_stall_ms = max(0.0, step_admission_ms - decode_step_ms)
    return {
        "config": {"arch": "olmo-1b.reduced", "n_slots": n_slots,
                   "max_len": max_len, "prompt_len": prompt_len,
                   "prefill_chunk": chunk, "max_new": max_new,
                   "smoke": smoke},
        "prefill_full_ms": round(prefill_full_ms, 3),
        "decode_step_ms": round(decode_step_ms, 3),
        "step_admission_ms": round(step_admission_ms, 3),
        "decode_stall_ms": round(decode_stall_ms, 3),
        "stall_below_full_prefill": decode_stall_ms < prefill_full_ms,
        "ttft": ttft,
    }


def _drain_burst(model, params, prompts, *, chunk, lanes, n_slots, max_len,
                 max_new) -> dict:
    """Enqueue every prompt at once, step until all finish; return TTFT
    percentiles and the total decode-stall of the drain."""
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=n_slots, max_len=max_len, prefill_chunk=chunk,
        chunks_per_step=lanes))
    # warmup: trace the chunk forward + pooled decode shapes off the clock
    warm = Request(uid=0, prompt=prompts[0], max_new=max_new + 8)
    eng.try_add(warm)
    while warm.phase in ("pending", "prefilling"):
        eng.step()
    # steady-state decode baseline while the warm slot is live
    decode_ms = statistics.median(_timed_step(eng)[0] for _ in range(8))
    eng.cancel(warm.uid)

    reqs = [Request(uid=i + 1, prompt=p, max_new=max_new)
            for i, p in enumerate(prompts)]
    t0 = time.perf_counter()
    for r in reqs:
        if not eng.try_add(r):
            raise RuntimeError(f"burst enqueue rejected uid {r.uid}")
    ttft_ms, admit_times = {}, []
    while not all(r.done for r in reqs):
        # only steps that actually ran admission forwards count as stalled
        # (a step spent waiting for a free slot — burst deeper than the
        # pool — is a plain decode step and would dilute the metric)
        f0 = eng.pipeline.forwards
        ms, _ = _timed_step(eng)
        if eng.pipeline.forwards > f0:
            admit_times.append(ms)
        for r in reqs:
            if r.uid not in ttft_ms and r.out:
                ttft_ms[r.uid] = (time.perf_counter() - t0) * 1e3
    # clamp at the drain level, not per step: per-step max(0, ...) would
    # rectify timer noise instead of letting it cancel
    total_stall = max(0.0, sum(admit_times) - len(admit_times) * decode_ms)
    ttfts = [ttft_ms[r.uid] for r in reqs]
    steps = [r.ttft_steps for r in reqs]
    return {
        "lanes": lanes,
        "decode_step_ms": round(decode_ms, 3),
        "admission_steps": len(admit_times),
        "total_stall_ms": round(total_stall, 3),
        "ttft_p50_ms": round(float(np.percentile(ttfts, 50)), 3),
        "ttft_p95_ms": round(float(np.percentile(ttfts, 95)), 3),
        "ttft_steps": steps,
        "ttft_steps_worst": max(steps),
    }


def run_burst(model, params, cfg, prompt_len: int, chunk: int, n_slots: int,
              max_new: int, n_burst: int, lanes: int, smoke: bool,
              arch: str = "olmo-1b.reduced") -> dict:
    """Burst admission: N queued prompts, sequential vs batched drain."""
    rng = np.random.default_rng(1)
    max_len = prompt_len + max_new + 8
    prompts = [_mk_prompt(rng, prompt_len, cfg.vocab_size)
               for _ in range(n_burst)]
    common = dict(chunk=chunk, n_slots=n_slots, max_len=max_len,
                  max_new=max_new)
    seq = _drain_burst(model, params, prompts, lanes=1, **common)
    bat = _drain_burst(model, params, prompts, lanes=lanes, **common)
    return {
        "config": {"arch": arch, "n_burst": n_burst, "prompt_len": prompt_len,
                   "prefill_chunk": chunk, "n_slots": n_slots,
                   "lanes": lanes, "max_new": max_new, "smoke": smoke},
        "sequential": seq,
        "batched": bat,
        # informational: ms-domain ratio (timer-noise-bound at smoke scale)
        "stall_ratio_ms": round(bat["total_stall_ms"]
                                / max(seq["total_stall_ms"], 1e-9), 3),
        # the gate: the deterministic steps-domain form of
        # "batched <= sequential stall" (see module docstring)
        "batched_stall_leq_sequential":
            bat["admission_steps"] <= seq["admission_steps"]
            and bat["ttft_steps_worst"] <= seq["ttft_steps_worst"],
    }


def run_overload(prompt_len: int, chunk: int, n_slots: int, max_new: int,
                 lanes: int, smoke: bool) -> dict:
    """SLO control loop under a 4x overload burst on a calibrated DSLOT
    model: the accuracy-vs-latency Pareto sweep per QoS tier.

    All gates are in the deterministic ENGINE-STEPS domain (wall-clock
    p95s on shared CI runners are noise; the step schedule is exact).
    """
    from repro.configs.base import DslotConfig

    cfg = dataclasses.replace(
        ARCHS["olmo-1b"].reduced(), act="relu", glu=False,
        dslot=DslotConfig(enabled=True, block_m=16, block_n=32, block_k=16,
                          act_scale=0.05))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(2))
    n_bits = cfg.dslot.n_bits
    rng = np.random.default_rng(2)
    max_len = prompt_len + max_new + 8
    n_burst = 4 * n_slots
    slo = SloConfig(queue_high_water=n_slots, shed_patience=2,
                    restore_patience=2, target_ttft_steps=4 * n_slots)
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=n_slots, max_len=max_len, prefill_chunk=chunk,
        chunks_per_step=lanes, slo=slo))
    cycle = [RESERVED, STANDARD, DEGRADABLE, DEGRADABLE]
    reqs = [Request(uid=i + 1,
                    prompt=_mk_prompt(rng, prompt_len, cfg.vocab_size),
                    max_new=max_new, tier=cycle[i % len(cycle)])
            for i in range(n_burst)]
    for r in reqs:
        if not eng.try_add(r):
            raise RuntimeError(f"overload enqueue rejected uid {r.uid}")
    reserved_floor_held = True
    steps = 0
    while not all(r.done for r in reqs):
        eng.step()
        steps += 1
        if eng.last_budget is not None:
            for slot, req in enumerate(eng.slot_req):
                if req is not None and req.tier == RESERVED \
                        and eng.last_budget[slot] < eng.slo.floor(RESERVED):
                    reserved_floor_held = False
    # drain: slack steps must restore every tier's level to its ceiling
    # (the stale TTFT window expires after ttft_idle_expiry idle steps,
    # then one tier-restore lands every restore_patience steps)
    for _ in range(slo.ttft_idle_expiry + 3 * n_bits * slo.restore_patience):
        eng.step()
    restored = eng.slo.levels == {n: t.ceiling
                                  for n, t in eng.slo.tiers.items()}
    # analytic drain bound on TTFT (steps domain, deterministic): every
    # request's first token waits at worst for the whole burst's admission
    # work (n_burst * chunks, one batched tick per step) plus the decode
    # occupancy of the slot waves ahead of it, plus slack for the tick the
    # merge lands on
    chunks_each = -(-prompt_len // chunk)
    ttft_bound = (n_burst * chunks_each
                  + (n_burst // n_slots + 1) * max_new + 8)
    pareto = {}
    for tier in (RESERVED, STANDARD, DEGRADABLE):
        rs = [r for r in reqs if r.tier == tier]
        ttfts = [r.ttft_steps for r in rs]
        bnd = [r.result.planes_bounded_mean for r in rs
               if r.result.planes_bounded_mean is not None]
        pareto[tier] = {
            "n_requests": len(rs),
            "mean_planes_used": round(float(np.mean(
                [r.result.planes_used_mean for r in rs])), 3),
            # weight-side planes never issued (static MSR bound) — the
            # request-independent saving that compounds with shedding
            "mean_planes_bounded": (round(float(np.mean(bnd)), 3)
                                    if bnd else None),
            "ttft_p50_steps": float(np.percentile(ttfts, 50)),
            "ttft_p95_steps": float(np.percentile(ttfts, 95)),
            "floor": eng.slo.floor(tier),
            "min_level": eng.slo.min_levels[tier],
        }
    p95_all = float(np.percentile([r.ttft_steps for r in reqs], 95))
    gates = {
        "reserved_floor_held": reserved_floor_held,
        "shed_occurred": eng.slo.shed_events > 0,
        "degraded_gracefully":
            pareto[DEGRADABLE]["mean_planes_used"] < float(n_bits),
        "ttft_p95_within_bound": p95_all <= ttft_bound,
        "budgets_restored_after_drain": restored,
    }
    return {
        "config": {"arch": "olmo-1b.reduced+dslot", "n_burst": n_burst,
                   "n_slots": n_slots, "prompt_len": prompt_len,
                   "prefill_chunk": chunk, "lanes": lanes,
                   "max_new": max_new, "n_bits": n_bits, "smoke": smoke,
                   "slo": {"queue_high_water": slo.queue_high_water,
                           "shed_patience": slo.shed_patience,
                           "restore_patience": slo.restore_patience,
                           "target_ttft_steps": slo.target_ttft_steps}},
        "drain_steps": steps,
        "ttft_p95_steps": p95_all,
        "ttft_bound_steps": ttft_bound,
        "pareto": pareto,
        "controller": eng.slo.summary(),
        "gates": gates,
        "ok": all(gates.values()),
    }


def run_chaos(prompt_len: int, chunk: int, n_slots: int, max_new: int,
              smoke: bool, mesh=None) -> dict:
    """Chaos scenario on the calibrated DSLOT model: a burst with an
    injected NaN (quarantine), a plan-driven cancel storm, and a transient
    lane failure — all from ONE deterministic ``FaultPlan``.

    Gates (all steps-domain / bit-exact, CI-safe):

    * ``no_crash`` — every ``step()`` returned (nothing raised) and the
      engine drained;
    * ``invariants_every_step`` — ``audit_engine`` returned [] after every
      single tick, faulted ones included;
    * ``quarantine_fired`` — exactly the poisoned request was evicted with
      ``phase == "quarantined"``;
    * ``cancel_storm_clean`` — every plan-cancelled request terminal, and
      the queue fully accounted for;
    * ``survivors_token_identical`` — every surviving request's stream is
      BIT-identical to the same engine run with no fault plan at all (the
      isolation + transactional-retry contract, end to end);
    * ``recovered_within_bound`` — the faulted drain finished within the
      analytic bound of the fault-free drain plus the injected stall steps.
    """
    from repro.configs.base import DslotConfig
    from repro.serve import FaultPlan, Fault, QUARANTINED, audit_engine

    cfg = dataclasses.replace(
        ARCHS["olmo-1b"].reduced(), act="relu", glu=False,
        dslot=DslotConfig(enabled=True, block_m=16, block_n=32, block_k=16,
                          act_scale=0.05))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    max_len = prompt_len + max_new + 8
    n_burst = 2 * n_slots
    victim_uid, storm_uids = 2, (3, 4)
    plan = FaultPlan(faults=(
        Fault(kind="lane_exception", step=1, count=1),     # transient
        Fault(kind="nan_logits", step=6, uid=victim_uid),  # poison
        Fault(kind="cancel", step=4, uid=storm_uids[0]),   # storm
        Fault(kind="cancel", step=4, uid=storm_uids[1]),
        Fault(kind="slow_step", step=2, value=0.001),
    ))
    prompts = [_mk_prompt(rng, prompt_len, cfg.vocab_size)
               for _ in range(n_burst)]

    def drive(faults):
        if mesh is not None:
            from repro.models import pspec
            pspec.set_mesh(None)           # engine installs the mesh itself
        eng = ServeEngine(model, params, ServeConfig(
            n_slots=n_slots, max_len=max_len, prefill_chunk=chunk,
            chunks_per_step=2, faults=faults, default_deadline_steps=200,
            mesh=mesh))
        reqs = [Request(uid=i + 1, prompt=p, max_new=max_new)
                for i, p in enumerate(prompts)]
        for r in reqs:
            if not eng.try_add(r):
                raise RuntimeError(f"chaos enqueue rejected uid {r.uid}")
        steps, invariants_ok, crashed = 0, True, False
        try:
            while not all(r.done for r in reqs):
                eng.step()
                steps += 1
                if audit_engine(eng):
                    invariants_ok = False
                if steps > 2000:
                    raise RuntimeError("chaos drain wedged")
        except Exception:
            crashed = True
        return eng, reqs, steps, invariants_ok, crashed

    ref_eng, ref_reqs, ref_steps, ref_inv, ref_crash = drive(None)
    eng, reqs, steps, invariants_ok, crashed = drive(plan)

    evicted = {victim_uid, *storm_uids}
    survivors = [r for r in reqs if r.uid not in evicted]
    ident = all(
        list(r.out) == list(ref.out)
        for r, ref in zip(reqs, ref_reqs) if r.uid not in evicted)
    victim = next(r for r in reqs if r.uid == victim_uid)
    stormed = [r for r in reqs if r.uid in storm_uids]
    # bound: the faulted drain saves the evicted requests' decode work but
    # pays the injected stall; it must land within the fault-free drain
    # plus slack for the retry + slow + quarantine steps
    recovery_bound = ref_steps + 8
    gates = {
        "no_crash": not crashed and not ref_crash,
        "invariants_every_step": invariants_ok and ref_inv,
        "quarantine_fired":
            victim.phase == QUARANTINED
            and [u for _, u in eng.quarantined] == [victim_uid],
        "cancel_storm_clean":
            all(r.done and r.phase == "cancelled" for r in stormed)
            and eng.queue_depth == 0,
        "lane_failure_absorbed":
            any(site == "admission" for _, site, _ in eng.errors),
        "survivors_token_identical":
            ident and all(r.phase == "done" and len(r.out) == max_new
                          for r in survivors),
        "recovered_within_bound": steps <= recovery_bound,
    }
    return {
        "config": {"arch": "olmo-1b.reduced+dslot", "n_burst": n_burst,
                   "n_slots": n_slots, "prompt_len": prompt_len,
                   "prefill_chunk": chunk, "max_new": max_new,
                   "smoke": smoke,
                   "mesh": None if mesh is None else dict(mesh.shape)},
        "plan": [{"kind": f.kind, "step": f.step, "slot": f.slot,
                  "uid": f.uid, "count": f.count, "value": f.value}
                 for f in plan.faults],
        "fired": eng.injector.summary()["fired"],
        "drain_steps": steps,
        "reference_drain_steps": ref_steps,
        "recovery_bound_steps": recovery_bound,
        "errors_absorbed": len(eng.errors),
        "quarantined": eng.quarantined,
        "timeouts": eng.timeouts,
        "gates": gates,
        "ok": all(gates.values()),
    }


def run_chaos_mesh(prompt_len: int, chunk: int, n_slots: int, max_new: int,
                   smoke: bool) -> dict | None:
    """The same chaos gates on a 2-shard tensor-parallel engine — skipped
    (returns None) when fewer than 2 devices are visible.  The CI chaos
    lane forces 2 host devices via XLA_FLAGS."""
    if len(jax.devices()) < 2:
        return None
    from repro.launch.mesh import make_test_mesh
    from repro.models import pspec

    try:
        return run_chaos(prompt_len, chunk, n_slots, max_new, smoke,
                         mesh=make_test_mesh(n_devices=2, model=2))
    finally:
        pspec.set_mesh(None)


def main():
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes / few reps for CI")
    ap.add_argument("--json", default="BENCH_serve.json")
    ap.add_argument("--prompt-len", type=int, default=None)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--burst", type=int, default=None,
                    help="burst size (default 4 smoke / 8)")
    ap.add_argument("--burst-lanes", type=int, default=4,
                    help="chunks_per_step for the batched burst drain")
    ap.add_argument("--chaos-only", action="store_true",
                    help="run only the chaos scenario (the CI chaos lane)")
    args = ap.parse_args()
    prompt_len = args.prompt_len if args.prompt_len is not None \
        else (48 if args.smoke else 192)
    chunk = args.chunk if args.chunk is not None \
        else (8 if args.smoke else 16)
    n_burst = args.burst if args.burst is not None \
        else (4 if args.smoke else 8)

    if args.chaos_only:
        out = {"chaos": run_chaos(3 * chunk, chunk, args.slots,
                                  args.max_new, args.smoke)}
        mesh_out = run_chaos_mesh(3 * chunk, chunk, args.slots,
                                  args.max_new, args.smoke)
        if mesh_out is not None:
            out["chaos_mesh"] = mesh_out
        with open(args.json, "w") as f:
            json.dump(out, f, indent=2)
        for key in ("chaos", "chaos_mesh"):
            if key not in out:
                print(f"{key}: skipped (needs >= 2 devices)")
                continue
            c = out[key]
            print(f"{key}: drained in {c['drain_steps']} steps "
                  f"(ref {c['reference_drain_steps']}, bound "
                  f"{c['recovery_bound_steps']}); "
                  f"{c['errors_absorbed']} errors absorbed, "
                  f"quarantined {c['quarantined']}")
            for gate, okv in c["gates"].items():
                print(f"  gate {gate}: {'OK' if okv else 'FAIL'}")
        print(f"wrote {args.json}")
        if not all(out[k]["ok"] for k in out):
            raise SystemExit(1)
        return

    cfg = ARCHS["olmo-1b"].reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    out = run(model, params, cfg, prompt_len, chunk, args.slots,
              args.max_new, args.smoke)
    out["burst"] = run_burst(model, params, cfg, prompt_len, chunk,
                             args.slots, args.max_new, n_burst,
                             args.burst_lanes, args.smoke)
    # every zoo stack batches now: the same burst drain + gate on a
    # sliding-window and a recurrent stack (ragged lanes, no serial path)
    for key, zoo_arch in (("burst_swa", "h2o-danube-3-4b"),
                          ("burst_ssm", "mamba2-780m")):
        zcfg = ARCHS[zoo_arch].reduced()
        zmodel = build_model(zcfg)
        zparams = zmodel.init(jax.random.PRNGKey(0))
        out[key] = run_burst(zmodel, zparams, zcfg, prompt_len, chunk,
                             args.slots, args.max_new, n_burst,
                             args.burst_lanes, args.smoke,
                             arch=f"{zoo_arch}.reduced")
    out["overload"] = run_overload(3 * chunk, chunk, args.slots,
                                   args.max_new, 2, args.smoke)
    out["chaos"] = run_chaos(3 * chunk, chunk, args.slots, args.max_new,
                             args.smoke)
    with open(args.json, "w") as f:
        json.dump(out, f, indent=2)
    print(f"full-prompt prefill     {out['prefill_full_ms']:9.2f} ms")
    print(f"decode step (no admit)  {out['decode_step_ms']:9.2f} ms")
    print(f"decode step (+1 chunk)  {out['step_admission_ms']:9.2f} ms")
    print(f"decode stall/admission  {out['decode_stall_ms']:9.2f} ms  "
          f"({'OK' if out['stall_below_full_prefill'] else 'FAIL'}: "
          f"< full prefill)")
    for t in out["ttft"]:
        print(f"  ttft uid={t['uid']}: {t['ttft_steps']} steps, "
              f"{t['ttft_ms']:.1f} ms")
    for bkey in ("burst", "burst_swa", "burst_ssm"):
        b = out[bkey]
        print(f"{bkey} [{b['config']['arch']}]")
        for mode in ("sequential", "batched"):
            m = b[mode]
            print(f"  {mode:10s}  lanes={m['lanes']}  "
                  f"ttft p50 {m['ttft_p50_ms']:8.1f} ms  "
                  f"p95 {m['ttft_p95_ms']:8.1f} ms  "
                  f"total stall {m['total_stall_ms']:8.1f} ms over "
                  f"{m['admission_steps']} stalled steps "
                  f"(worst ttft {m['ttft_steps_worst']} steps)")
        print(f"  stall ratio ms (informational) {b['stall_ratio_ms']:.3f}; "
              f"stalled-steps {b['batched']['admission_steps']} vs "
              f"{b['sequential']['admission_steps']}, worst ttft "
              f"{b['batched']['ttft_steps_worst']} vs "
              f"{b['sequential']['ttft_steps_worst']} steps "
              f"({'OK' if b['batched_stall_leq_sequential'] else 'FAIL'}: "
              f"batched <= sequential)")
    o = out["overload"]
    print(f"overload 4x burst ({o['config']['n_burst']} reqs, "
          f"{o['drain_steps']} steps to drain; ttft p95 "
          f"{o['ttft_p95_steps']:.0f} <= bound {o['ttft_bound_steps']}):")
    for tier, p in o["pareto"].items():
        print(f"  {tier:10s}  planes-used {p['mean_planes_used']:5.2f} "
              f"(floor {p['floor']}, min level {p['min_level']})  "
              f"ttft p95 {p['ttft_p95_steps']:5.0f} steps  "
              f"[{p['n_requests']} reqs]")
    c = o["controller"]
    print(f"  controller: {c['shed_events']} sheds / "
          f"{c['restore_events']} restores; levels {c['levels']}")
    for gate, okv in o["gates"].items():
        print(f"  gate {gate}: {'OK' if okv else 'FAIL'}")
    ch = out["chaos"]
    print(f"chaos: drained in {ch['drain_steps']} steps "
          f"(ref {ch['reference_drain_steps']}, bound "
          f"{ch['recovery_bound_steps']}); {ch['errors_absorbed']} errors "
          f"absorbed, quarantined {ch['quarantined']}")
    for gate, okv in ch["gates"].items():
        print(f"  gate {gate}: {'OK' if okv else 'FAIL'}")
    print(f"wrote {args.json}")
    if not out["stall_below_full_prefill"]:
        raise SystemExit(1)
    if not all(out[k]["batched_stall_leq_sequential"]
               for k in ("burst", "burst_swa", "burst_ssm")):
        raise SystemExit(1)
    if not o["ok"]:
        raise SystemExit(1)
    if not ch["ok"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
