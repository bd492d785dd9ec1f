"""Chip smoke test: drive DSLOT serving once on a TPU through its entry points.

    python chip_smoke.py             # one chip: kernel, dslot-generate,
                                     # serve, serve-dslot
    python chip_smoke.py --chips 4   # four chips: the serve-dslot engine on a
                                     # 4-way "model" mesh vs one chip

Everything runs in this one process (it holds the chip; it starts no child
that touches JAX).  Weights and data are random, made from ``--seed``;
widths are the published ones.  Phases (one chip):

* kernel         ``dslot_execute`` on the compiled Pallas kernel at
                 olmo-1b's (K=2048, N=8192) and seamless-m4t-medium's
                 (K=1024, N=4096) up-projection widths, decode (8) and
                 prefill (2048) rows, scalar and per-row plane budgets,
                 against the ``backend="jnp"`` replay of the same prepared
                 weights on the same chip.
* dslot-generate seamless-m4t-medium (ReLU, no GLU: the digit-serial MLP
                 runs unmodified) through ``generate`` with the Pallas
                 kernel and per-request budgets [8, 8, 6, 4].
* serve          olmo-1b through ``ServeEngine``: 8 slots, 8 requests with
                 64-512 token prompts, chunked admission, drained.
* serve-dslot    the same engine on the ReLU/no-GLU variant of olmo-1b's
                 widths with a calibrated activation scale and mixed
                 per-request budgets.

Each phase prints one line of what it checked.  A phase fails, and the
script exits non-zero without a result line, when the platform is not a
TPU, a compiled DSLOT step holds no ``tpu_custom_call``, the engine logged
an error, a request ended in any phase but ``done``, a logit is not
finite, or a stated tolerance does not hold.  The last line of stdout is
``{"ok": true, "device": {...}}``.  Wall times printed are set-up plus
compile plus run of a single pass: they are not benchmark numbers.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# kernel vs jnp replay: both sum the same exact digit x weight products in
# f32 (weights are bf16-valued, digits are -1/0/1, plane scales are powers
# of two), so only the summation order differs
KERNEL_RTOL = 1e-4          # max |pallas - jnp| / max |jnp|
PLANES_MIN_AGREE = 0.99     # tiles whose planes_used match the replay
# Pallas vs jnp replay through a whole bf16 model: a 1-ulp bf16 flip of
# one MLP output can propagate through every later layer
LOGITS_RTOL = 5e-2          # max |logits diff| / max |ref logits|

KERNEL_WIDTHS = {"olmo-1b": (2048, 8192), "seamless-m4t-medium": (1024, 4096)}
KERNEL_ROWS = {"decode": 8, "prefill": 2048}
GEN_BUDGETS = [8, 8, 6, 4]
GEN_PROMPT, GEN_NEW = 16, 16
SERVE = dict(n_slots=8, n_requests=8, prompt_lo=64, prompt_hi=512,
             max_new=32, prefill_chunk=128, chunks_per_step=2)
DSLOT_BUDGETS = [8, 6, 4, 8, 5, 7, 3, 8]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_custom_call(hlo_text: str, what: str) -> None:
    """The compiled program runs the Pallas kernel, not an interpreter."""
    check("tpu_custom_call" in hlo_text,
          f"{what}: compiled program holds no tpu_custom_call")


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _bf16_valued(a):
    import jax.numpy as jnp
    return a.astype(jnp.bfloat16).astype(jnp.float32)


# ------------------------------------------------------------ kernel phase

def phase_kernel(seed: int, widths=KERNEL_WIDTHS, rows=KERNEL_ROWS,
                 block: int = 128) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import calibrate_scale, dslot_execute, dslot_prepare

    execute = jax.jit(lambda p, x, n: dslot_execute(p, x, n_planes=n))
    for arch, (K, N) in widths.items():
        kw, kb, kx = jax.random.split(jax.random.PRNGKey(seed), 3)
        # non-negative activations and per-column weight shifts that move
        # the column means over [-8, 2] output standard deviations: after
        # the column sort whole tiles go provably negative, so early
        # termination has tiles to skip
        shift = jax.random.uniform(kb, (N,), minval=-8.0, maxval=2.0) \
            / (0.8 * K)
        w = _bf16_valued(jax.random.normal(kw, (K, N)) * K ** -0.5 + shift)
        prep = dslot_prepare(w, n_bits=8, relu=True, signed=True,
                             sort_columns=True, block_m=block, block_n=block,
                             backend="pallas")
        ref_prep = dataclasses.replace(prep, backend="jnp")
        for label, M in rows.items():
            x = jnp.abs(jax.random.normal(jax.random.fold_in(kx, M), (M, K)))
            p = prep.with_scale(calibrate_scale(x, signed=True))
            rp = ref_prep.with_scale(p.x_scale)
            rng = np.random.default_rng(seed + M)
            for budget in ("scalar", "per-row"):
                npl = (jnp.asarray(6, jnp.int32) if budget == "scalar" else
                       jnp.asarray(rng.integers(1, 9, M), jnp.int32))
                compiled = execute.lower(p, x, npl).compile()
                require_custom_call(compiled.as_text(),
                                    f"kernel {arch} {label}")
                out, st = compiled(p, x, npl)
                ref, rst = execute(rp, x, npl)
                out, ref = np.asarray(out), np.asarray(ref)
                check(out.shape == (M, N), f"kernel output shape {out.shape}")
                check(bool(np.isfinite(out).all()), "kernel output not finite")
                err = float(np.abs(out - ref).max()
                            / max(float(np.abs(ref).max()), 1e-30))
                agree = float(np.mean(np.asarray(st.planes_used)
                                      == np.asarray(rst.planes_used)))
                say("kernel", f"{arch} K={K} N={N} M={M} ({label}) "
                    f"n_planes={budget}: tpu_custom_call present, "
                    f"max|pallas-jnp|/max|jnp|={err:.3e} "
                    f"(tol {KERNEL_RTOL:g}), planes_used agreement "
                    f"{agree:.4f} (min {PLANES_MIN_AGREE:g}), "
                    f"skipped_frac={float(st.skipped_frac):.4f}")
                check(err <= KERNEL_RTOL,
                      f"kernel {arch} M={M} {budget}: error {err:.3e}")
                check(agree >= PLANES_MIN_AGREE,
                      f"kernel {arch} M={M} {budget}: planes agreement "
                      f"{agree:.4f}")


# --------------------------------------------------- dslot-generate phase

def phase_dslot_generate(seed: int, cfg=None, block: int = 128,
                         prompt_len: int = GEN_PROMPT,
                         max_new: int = GEN_NEW) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import DslotConfig
    from repro.configs.registry import ARCHS
    from repro.launch.serve import make_batch
    from repro.models.model_zoo import build_model
    from repro.runtime import precision_scope
    from repro.serve import generate

    base = cfg or ARCHS["seamless-m4t-medium"]
    dslot = DslotConfig(enabled=True, use_pallas=True, block_m=block,
                        block_n=block)
    cfg = dataclasses.replace(base, dslot=dslot)
    ref_cfg = dataclasses.replace(
        base, dslot=dataclasses.replace(dslot, use_pallas=False))
    model, ref_model = build_model(cfg), build_model(ref_cfg)
    params = model.init(jax.random.PRNGKey(seed))
    batch = make_batch(cfg, len(GEN_BUDGETS), prompt_len,
                       jax.random.PRNGKey(seed + 1))
    budgets = jnp.asarray(GEN_BUDGETS, jnp.int32)
    pp = model.prepare_dslot(params)
    rp = ref_model.prepare_dslot(params)

    def prefill_logits(m):
        def fn(p, b, n):
            with precision_scope(n):
                return m.prefill(p, b)[0]
        return jax.jit(fn)

    logits = np.asarray(prefill_logits(model)(pp, batch, budgets),
                        np.float32)
    ref = np.asarray(prefill_logits(ref_model)(rp, batch, budgets),
                     np.float32)
    check(bool(np.isfinite(logits).all()), "prefill logits not finite")
    err = float(np.abs(logits - ref).max() / max(float(np.abs(ref).max()),
                                                 1e-30))
    top = np.argmax(logits.reshape(len(GEN_BUDGETS), -1), -1)
    top_ref = np.argmax(ref.reshape(len(GEN_BUDGETS), -1), -1)

    gen = jax.jit(lambda p, b, n: (
        lambda r: (r.tokens, r.planes_used_mean))(
            generate(model, p, b, max_new, n_planes=n)))
    compiled = gen.lower(pp, batch, budgets).compile()
    require_custom_call(compiled.as_text(), "dslot-generate")
    toks, used = compiled(pp, batch, budgets)
    toks, used = np.asarray(toks), np.asarray(used)
    check(toks.shape == (len(GEN_BUDGETS), max_new),
          f"generated shape {toks.shape}")
    check(bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          "generated token outside the vocabulary")
    check(bool(np.isfinite(used).all()), "planes_used_mean not finite")
    check(bool((used <= budgets + 1e-6).all()),
          f"planes used {used} exceed the granted budgets")
    say("dslot-generate",
        f"{base.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
        f"layers={cfg.n_layers}+{cfg.encoder_layers} pallas blocks "
        f"{block}x{block}, n_planes={GEN_BUDGETS}: tpu_custom_call present, "
        f"tokens {toks.shape} in vocab, prefill logits finite, "
        f"max|pallas-jnp|/max|jnp| logits={err:.3e} (tol {LOGITS_RTOL:g}), "
        f"first-token agreement {float(np.mean(top == top_ref)):.2f}, "
        f"planes_used_mean={np.round(used, 3).tolist()}")
    check(err <= LOGITS_RTOL, f"dslot-generate logits error {err:.3e}")


# ----------------------------------------------------------- serve phases

def _requests(seed: int, vocab: int, budgets=None, serve=SERVE):
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [Request(uid=i,
                    prompt=rng.integers(0, vocab, int(rng.integers(
                        serve["prompt_lo"], serve["prompt_hi"] + 1))
                                        ).astype(np.int32),
                    max_new=serve["max_new"],
                    n_planes=None if budgets is None else budgets[i])
            for i in range(serve["n_requests"])]


def _serve(model, params, reqs, serve=SERVE, mesh=None):
    """One engine over ``reqs``, drained; returns the engine."""
    from repro.models import pspec
    from repro.serve import ServeConfig, ServeEngine

    pspec.set_mesh(None)            # the engine installs its own mesh
    max_len = serve["prompt_hi"] + serve["max_new"]
    eng = ServeEngine(model, params, ServeConfig(
        n_slots=serve["n_slots"], max_len=max_len,
        prefill_chunk=serve["prefill_chunk"],
        chunks_per_step=serve["chunks_per_step"], mesh=mesh))
    for r in reqs:
        check(eng.try_add(r), f"request {r.uid} refused")
    eng.drain()
    return eng


def _check_engine(eng, reqs, phase: str) -> None:
    check(not eng.errors, f"{phase}: engine errors {eng.errors[:3]}")
    check(not eng.quarantined,
          f"{phase}: non-finite logits quarantined {eng.quarantined}")
    bad = [(r.uid, r.phase) for r in reqs if r.phase != "done"]
    check(not bad, f"{phase}: requests not done {bad}")
    vocab = eng.model.cfg.vocab_size
    for r in reqs:
        check(len(r.out) == r.max_new, f"{phase}: uid {r.uid} emitted "
              f"{len(r.out)} of {r.max_new}")
        check(all(0 <= t < vocab for t in r.out),
              f"{phase}: uid {r.uid} token outside the vocabulary")


def _decode_hlo(eng) -> str:
    """Text of the engine's compiled pooled decode step."""
    import jax.numpy as jnp
    toks = jnp.asarray(eng.next_tok[:, None])
    return eng._decode.lower(eng.params, eng.state, toks,
                             eng._budget_vector()).compile().as_text()


def phase_serve(seed: int, cfg=None, serve=SERVE) -> None:
    import jax

    from repro.configs.registry import ARCHS
    from repro.models.model_zoo import build_model

    cfg = cfg or ARCHS["olmo-1b"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    reqs = _requests(seed, cfg.vocab_size, serve=serve)
    t0 = time.perf_counter()
    eng = _serve(model, params, reqs, serve)
    dt = time.perf_counter() - t0
    _check_engine(eng, reqs, "serve")
    say("serve", f"{cfg.name} d_model={cfg.d_model} d_ff={cfg.d_ff} "
        f"layers={cfg.n_layers} {cfg.dtype}: {len(reqs)} requests, prompts "
        f"{sorted(len(r.prompt) for r in reqs)}, {eng.steps} engine steps, "
        f"all done with {serve['max_new']} tokens each, no engine errors, "
        f"logits finite (nothing quarantined); wall {dt:.1f}s incl. "
        f"compile (not a benchmark)")


def dslot_variant(seed: int, cfg=None, block: int = 128):
    """The ReLU/no-GLU variant of olmo-1b's widths with the Pallas DSLOT
    MLP, its activation scale calibrated on the normalized embeddings of a
    seeded calibration prompt (the up-projection's input is a normalized
    residual row)."""
    import jax

    from repro.configs.base import DslotConfig
    from repro.configs.registry import ARCHS
    from repro.kernels.ops import calibrate_scale
    from repro.models.layers import apply_norm, embed_tokens
    from repro.models.model_zoo import build_model

    base = dataclasses.replace(cfg or ARCHS["olmo-1b"], act="relu",
                               glu=False)
    model = build_model(base)
    params = model.init(jax.random.PRNGKey(seed))
    calib = jax.random.randint(jax.random.PRNGKey(seed + 7), (512,), 0,
                               base.vocab_size)
    x = apply_norm(params.get("final_norm", {}),
                   embed_tokens(params["embed"], calib, base), base)
    scale = float(calibrate_scale(x.astype("float32"), signed=True))
    cfg = dataclasses.replace(base, dslot=DslotConfig(
        enabled=True, use_pallas=True, block_m=block, block_n=block,
        act_scale=scale))
    return build_model(cfg), params, x


def phase_serve_dslot(seed: int, cfg=None, serve=SERVE,
                      block: int = 128) -> None:
    model, params, _ = dslot_variant(seed, cfg, block)
    dcfg = model.cfg
    reqs = _requests(seed, dcfg.vocab_size, DSLOT_BUDGETS, serve)
    t0 = time.perf_counter()
    eng = _serve(model, params, reqs, serve)
    dt = time.perf_counter() - t0
    _check_engine(eng, reqs, "serve-dslot")
    require_custom_call(_decode_hlo(eng), "serve-dslot decode step")
    used = [round(float(r.result.planes_used_mean), 3) for r in reqs]
    check(all(u <= b + 1e-6 for u, b in zip(used, DSLOT_BUDGETS)),
          f"serve-dslot planes used {used} exceed budgets {DSLOT_BUDGETS}")
    say("serve-dslot", f"ReLU/no-GLU variant of {dcfg.name}'s widths "
        f"(d_model={dcfg.d_model} d_ff={dcfg.d_ff} layers={dcfg.n_layers}), "
        f"act_scale={dcfg.dslot.act_scale:.5f} calibrated, pallas blocks "
        f"{block}x{block}, n_planes={DSLOT_BUDGETS}: decode step holds "
        f"tpu_custom_call, {eng.steps} engine steps, all done, no engine "
        f"errors, logits finite, planes_used_mean={used}; wall {dt:.1f}s "
        f"incl. compile (not a benchmark)")


# ----------------------------------------------------------- four chips

def phase_tp(seed: int, n_chips: int, cfg=None, serve=SERVE,
             block: int = 128) -> None:
    """serve-dslot on a ``n_chips``-way "model" mesh vs one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import DslotWeights, dslot_execute
    from repro.launch.mesh import make_test_mesh
    from repro.models import pspec

    check(len(jax.devices()) >= n_chips,
          f"need {n_chips} devices, have {len(jax.devices())}")
    model, params, x = dslot_variant(seed, cfg, block)
    x = x.astype(jnp.float32)
    mesh = make_test_mesh(n_devices=n_chips, model=n_chips)
    devices = set(mesh.devices.flat)

    ref_reqs = _requests(seed, model.cfg.vocab_size, DSLOT_BUDGETS, serve)
    ref = _serve(model, params, ref_reqs, serve)
    _check_engine(ref, ref_reqs, "tp one-chip")
    check(pspec.tp_size() == 1, "one-chip engine saw a tensor-parallel mesh")
    reqs = _requests(seed, model.cfg.vocab_size, DSLOT_BUDGETS, serve)
    eng = _serve(model, params, reqs, serve, mesh=mesh)
    _check_engine(eng, reqs, f"tp {n_chips}-chip")
    require_custom_call(_decode_hlo(eng), "tp decode step")

    # nothing may sit on device 0 alone: weights and the KV pool before
    # and after serving span every device of the mesh
    narrow = [a.shape for a in jax.tree.leaves((eng.params, eng.state))
              if set(a.sharding.device_set) != devices]
    check(not narrow, f"{len(narrow)} arrays not on all {n_chips} devices: "
          f"{narrow[:4]}")

    def first_layer(tree):
        found = [d for d in jax.tree.leaves(
            tree, is_leaf=lambda n: isinstance(n, DslotWeights))
            if isinstance(d, DslotWeights)]
        d = found[0]
        return jax.tree.map(lambda a: a[0], d) if d.w.ndim == 3 else d

    one, shard = first_layer(ref.params), first_layer(eng.params)
    check(shard.mesh is not None and one.mesh is None,
          "layer-0 prepared weights carry the wrong mesh")
    rows = jnp.asarray(np.random.default_rng(seed).integers(
        1, 9, x.shape[0]), jnp.int32)
    out1, st1 = dslot_execute(one, x, n_planes=rows)
    outn, stn = dslot_execute(shard, x, n_planes=rows)
    check(set(outn.sharding.device_set) == devices,
          "sharded up-projection output is not on the mesh")
    same = bool(np.array_equal(np.asarray(out1), np.asarray(outn))
                and np.array_equal(np.asarray(st1.planes_used),
                                   np.asarray(stn.planes_used)))
    toks = [t for r in ref_reqs for t in r.out]
    got = [t for r in reqs for t in r.out]
    agree = float(np.mean(np.asarray(toks) == np.asarray(got)))
    say(f"tp-{n_chips}", f"serve-dslot engine on a {n_chips}-way 'model' "
        f"mesh vs one chip, same process: layer-0 DSLOT up-projection "
        f"({x.shape[0]}x{one.d_in} -> {one.d_out}, per-row budgets) output "
        f"and planes_used bit-identical={same}; every weight and KV array "
        f"on all {n_chips} devices; decode step holds tpu_custom_call; "
        f"served token agreement {agree:.4f} over {len(toks)} tokens")
    check(same, "sharded DSLOT up-projection differs from one chip")


# ------------------------------------------------------------------ main

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the tensor-parallel serve-dslot check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    try:
        import jax

        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the program ({e}); run from the "
              "repository root", file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    say("device", f"{dev.platform} {dev.device_kind} x{len(jax.devices())}, "
        f"jax {jax.__version__}, compile cache {cache}")

    if args.chips == 4:
        phases = [("tp-4", lambda: phase_tp(args.seed, 4))]
    else:
        phases = [("kernel", lambda: phase_kernel(args.seed)),
                  ("dslot-generate", lambda: phase_dslot_generate(args.seed)),
                  ("serve", lambda: phase_serve(args.seed)),
                  ("serve-dslot", lambda: phase_serve_dslot(args.seed))]
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:                       # noqa: BLE001 — report, stop
            traceback.print_exc()
            say(name, "FAILED")
            return 1
        say(name, f"passed in {time.perf_counter() - t0:.1f}s wall")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
