"""Tensor-parallel DSLOT scaling benchmark -> ``BENCH_distributed.json``.

Measures the N-sharded ``dslot_execute`` (``kernels/ops.py`` tensor
parallelism) across 1/2/4/8 forced host devices on one CPU — wall-clock
per shard count, measured speedup vs 1 shard, and the
``launch.roofline.predict_tp_scaling`` model prediction next to it so
model drift is visible.  Also times the expert-parallel MoE dispatch
(``distributed/expert_parallel.apply_moe_ep``) for the two MoE zoo configs
(``mixtral_8x22b``, ``granite_moe_1b_a400m``, reduced shapes) under
per-expert digit-plane budgets.

CPU host devices share one socket, so measured "scaling" here is a
correctness-shaped smoke curve, not a hardware claim — the CI gate is
BIT-IDENTITY of every sharded result against the unsharded reference
(exit 1 on divergence), with the timing published for trend tracking.

This file must set the device-count override BEFORE jax initializes, so
all jax imports are deferred into main().

Standalone CLI (used by the CI multi-device lane):
    python benchmarks/bench_distributed.py [--smoke] [--json out.json]
"""

from __future__ import annotations

import argparse
import json
import os
import time


def _timeit(fn, *args, iters=3):
    import jax
    jax.tree.leaves(fn(*args))[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def bench_tp_matmul(shape, device_counts, iters):
    """Sharded dslot_execute: bit-identity gate + scaling curve."""
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.ops import dslot_execute, dslot_prepare
    from repro.launch.mesh import make_test_mesh
    from repro.launch.roofline import predict_tp_scaling

    m, k, n = shape
    rng = np.random.default_rng(0)
    w = rng.normal(size=(k, n)).astype(np.float32)
    w[:, : n // 8] = 0.0
    x = rng.normal(size=(m, k)).astype(np.float32).clip(0)
    npl = jnp.asarray(rng.integers(4, 9, size=m), jnp.int32)
    kw = dict(n_bits=8, relu=True, sort_columns=True,
              block_m=32, block_n=32, block_k=32)

    ref = None
    rows, mismatches = [], 0
    for s in device_counts:
        mesh = None if s == 1 else make_test_mesh(n_devices=s, model=s)
        prep = dslot_prepare(w, mesh=mesh, **kw)
        us = _timeit(lambda p=prep: dslot_execute(p, x, n_planes=npl),
                     iters=iters)
        out, _ = dslot_execute(prep, x, n_planes=npl)
        out = np.asarray(out)
        if ref is None:
            ref, t1 = out, us
        elif not np.array_equal(out, ref):
            mismatches += 1
        rows.append({
            "devices": s, "wall_us": us,
            "measured_speedup": t1 / us,
            "predicted_speedup": predict_tp_scaling(
                m, k, n, s)["predicted_speedup"],
            "bit_identical": ref is not None and np.array_equal(out, ref),
        })
    return {"shape": {"m": m, "k": k, "n": n}, "curve": rows}, mismatches


def bench_moe_ep(arch_names, iters):
    """Expert-parallel MoE under per-expert plane budgets (8-way mesh)."""
    import dataclasses
    import importlib

    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.distributed.expert_parallel import apply_moe_ep
    from repro.launch.mesh import make_test_mesh
    from repro.models.moe import apply_moe, init_moe

    mesh = make_test_mesh(model=8)
    out = {}
    for name in arch_names:
        cfg = importlib.import_module(f"repro.configs.{name}").CONFIG
        cfg = dataclasses.replace(cfg.reduced(), n_experts=8, top_k=2)
        p = init_moe(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model),
                              jnp.float32) * 0.5
        budgets = jnp.asarray([8, 8, 6, 6, 5, 5, 4, 4], jnp.int32)
        y_dense, _ = apply_moe(p, x, cfg)
        y_ep, _ = apply_moe_ep(p, x, cfg, mesh)
        y_bud, _ = apply_moe_ep(p, x, cfg, mesh, expert_planes=budgets)
        out[name] = {
            "ep_wall_us": _timeit(
                lambda: apply_moe_ep(p, x, cfg, mesh), iters=iters),
            "ep_budget_wall_us": _timeit(
                lambda: apply_moe_ep(p, x, cfg, mesh,
                                     expert_planes=budgets), iters=iters),
            "ep_vs_dense_maxerr": float(
                jnp.abs(y_ep - y_dense).max()),
            "budget_vs_ep_maxerr": float(jnp.abs(y_bud - y_ep).max()),
        }
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small shapes / few iters for the CI lane")
    ap.add_argument("--json", default="BENCH_distributed.json")
    ap.add_argument("--devices", type=int, nargs="+",
                    default=[1, 2, 4, 8])
    args = ap.parse_args()

    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    import jax

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    n_dev = len(jax.devices())
    counts = [c for c in args.devices if c <= n_dev]
    if len(counts) < 2:
        raise SystemExit(
            f"need >=2 usable device counts, have {n_dev} devices — set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8")

    shape = (64, 128, 256) if args.smoke else (256, 512, 1024)
    iters = 2 if args.smoke else 5
    tp, mismatches = bench_tp_matmul(shape, counts, iters)
    moe = bench_moe_ep(["mixtral_8x22b", "granite_moe_1b_a400m"],
                       iters=iters)

    rec = {"backend": jax.default_backend(), "host_devices": n_dev,
           "smoke": bool(args.smoke), "tp_matmul": tp, "moe_ep": moe}
    with open(args.json, "w") as fh:
        json.dump(rec, fh, indent=2)

    print(f"written to {args.json}")
    for r in tp["curve"]:
        print(f"  devices={r['devices']} wall={r['wall_us']:.0f}us "
              f"measured x{r['measured_speedup']:.2f} "
              f"predicted x{r['predicted_speedup']:.2f} "
              f"bit_identical={r['bit_identical']}")
    for name, m in moe.items():
        print(f"  moe_ep {name}: {m['ep_wall_us']:.0f}us "
              f"(budgets {m['ep_budget_wall_us']:.0f}us, "
              f"vs dense maxerr {m['ep_vs_dense_maxerr']:.2e})")
        if m["ep_vs_dense_maxerr"] > 2e-2:
            raise SystemExit(f"EP MoE diverged from dense for {name}")
    if mismatches:
        raise SystemExit(f"{mismatches} sharded results diverged "
                         "from the unsharded reference")


if __name__ == "__main__":
    main()
