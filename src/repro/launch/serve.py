"""Serving entry point: batched generation with optional DSLOT digit-serial
execution (the paper's engine as a serving-time switch).

    python -m repro.launch.serve --arch seamless-m4t-medium --reduced \
        --batch 4 --max-new 16 [--dslot --n-planes 6]

``--dslot`` turns on digit-plane execution (with early negative termination)
for every ReLU MLP, at 128x128 blocks: the compiled Pallas kernel on a TPU,
the jnp replay elsewhere.  ``--n-planes`` is the runtime precision knob
(named like the ``generate(..., n_planes=...)`` / ``Request.n_planes``
argument it sets; ``--planes`` is kept as a hidden alias).
"""

import argparse
import dataclasses
import time


def make_batch(cfg, batch: int, prompt_len: int, key) -> dict:
    """Seeded generation inputs for ``cfg``: random prompt tokens, plus the
    stub frontend frames and encoder source embeddings the audio / enc-dec
    families take."""
    import jax

    out = {"tokens": jax.random.randint(
        key, (batch, prompt_len), 0, cfg.vocab_size)}
    if cfg.frontend:
        out["frontend"] = jax.random.normal(
            key, (batch, cfg.frontend_len, cfg.d_model)) * 0.02
    if cfg.family == "encdec":
        out["src_embeds"] = jax.random.normal(
            key, (batch, 8, cfg.d_model)) * 0.02
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--dslot", action="store_true")
    ap.add_argument("--n-planes", "--planes", type=int, default=8,
                    dest="n_planes")
    args = ap.parse_args()

    import jax

    from repro.configs.base import DslotConfig
    from repro.launch.compile_cache import enable_compile_cache
    from repro.configs.registry import get_arch
    from repro.models import stats
    from repro.models.model_zoo import build_model
    from repro.serve.engine import generate

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.dslot:
        cfg = dataclasses.replace(cfg, dslot=DslotConfig(
            enabled=True, n_planes=args.n_planes,
            use_pallas=jax.default_backend() == "tpu"))
        if cfg.act != "relu" or cfg.glu:
            print(f"note: {cfg.name} has {cfg.act}/glu MLPs — DSLOT early "
                  "termination applies only to ReLU MLPs; running the "
                  "standard path for those layers.")

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, args.batch, args.prompt_len,
                       jax.random.PRNGKey(1))

    t0 = time.time()
    toks = generate(model, params, batch, args.max_new).tokens
    toks.block_until_ready()
    dt = time.time() - t0
    with stats.collect() as sink:
        if args.dslot:
            model.forward(params, batch)   # eager pass for observable stats
    print(f"arch={cfg.name} generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.max_new / dt:.1f} tok/s)")
    print("sample:", jax.device_get(toks[0])[:12], "...")
    if sink.get("mlp_dslot_skipped_frac"):
        vals = [float(v) for v in jax.device_get(
            sink["mlp_dslot_skipped_frac"])]
        print(f"DSLOT: {len(vals)} digit-serial MLP calls, mean "
              f"{sum(vals)/len(vals):.1%} MXU passes skipped "
              f"(D={args.n_planes} planes)")


if __name__ == "__main__":
    main()
