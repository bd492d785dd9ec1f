"""Production training entry point.

    python -m repro.launch.train --arch olmo-1b [--reduced] --steps 100 \
        --ckpt-dir /tmp/ckpt [--devices 8 --mesh 4x2]

Wires together: config registry -> model zoo -> FSDPxTP shardings -> data
pipeline -> grad-accumulation train step -> resilient loop (async sharded
checkpoints, restore-on-restart, straggler monitor).  On the CPU container
use ``--reduced`` (full configs need the real fleet); on hardware, drop it
and point --mesh at the pod slice.
"""

import argparse
import os


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--devices", type=int, default=0,
                    help="host-device override (set BEFORE jax init)")
    ap.add_argument("--mesh", default="", help="e.g. 4x2 = data x model")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.configs.registry import get_arch
    from repro.data.pipeline import TokenPipeline, make_global_batch
    from repro.launch.mesh import auto_mesh
    from repro.models import pspec
    from repro.models.model_zoo import build_model
    from repro.optim.adamw import AdamWConfig
    from repro.train.sharding import (make_batch_shardings,
                                      make_param_shardings)
    from repro.train.step import init_train_state, make_train_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)

    mesh = None
    if args.mesh:
        shape = tuple(int(x) for x in args.mesh.split("x"))
        names = ("data", "model")[-len(shape):]
        mesh = auto_mesh(shape, names)
        pspec.set_mesh(mesh)

    state = init_train_state(model, jax.random.PRNGKey(0))
    opt = AdamWConfig(peak_lr=args.lr, warmup_steps=min(100, args.steps // 10),
                      decay_steps=args.steps)
    step_fn = make_train_step(model, opt)

    pipe = TokenPipeline(vocab=cfg.vocab_size, seq_len=args.seq_len,
                         global_batch=args.global_batch,
                         microbatches=args.microbatches)

    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck and ck.latest_step() is not None:
        state = ck.restore(ck.latest_step(), state)
        print(f"restored from step {int(state.step)}")

    if mesh is not None:
        psh = make_param_shardings(mesh, state.params)
        ssh = type(state)(
            params=psh,
            opt=type(state.opt)(m=make_param_shardings(mesh, state.opt.m),
                                v=make_param_shardings(mesh, state.opt.v),
                                count=NamedSharding(mesh, P())),
            step=NamedSharding(mesh, P()))
        bsh = make_batch_shardings(
            mesh, jax.eval_shape(lambda: jax.tree.map(
                jnp.asarray, pipe.next_host_batch())),
            args.global_batch, batch_axis=1)
        with mesh:
            step_fn = jax.jit(step_fn, in_shardings=(ssh, bsh),
                              donate_argnums=(0,))
    else:
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={len(jax.devices())}")

    start = int(state.step)
    for s in range(start, args.steps):
        host = pipe.next_host_batch()
        if mesh is not None:
            batch = make_global_batch(mesh, host, bsh)
        else:
            batch = jax.tree.map(jnp.asarray, host)
        state, m = step_fn(state, batch)
        if (s + 1) % args.log_every == 0 or s == start:
            print(f"step {s+1:5d}  loss {float(m['loss']):.4f}  "
                  f"gnorm {float(m['grad_norm']):.3f}  "
                  f"lr {float(m['lr']):.2e}", flush=True)
        if ck and (s + 1) % args.ckpt_every == 0:
            ck.save_async(s + 1, state)
    if ck:
        ck.wait()
        ck.save(args.steps, state)
    print("done.")


if __name__ == "__main__":
    main()
