"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without any real hardware:
  * the sharding config is coherent (GSPMD partitions the whole step),
  * the per-device memory fits a TPU v5e (``compiled.memory_analysis()``),
  * and it extracts the roofline inputs (``cost_analysis`` FLOPs/bytes +
    collective bytes parsed from the optimized HLO).

Usage:
    python -m repro.launch.dryrun --arch deepseek-67b --shape train_4k \
        [--multi-pod] [--out experiments/dryrun]
    python -m repro.launch.dryrun --all [--multi-pod]   # every live cell

Results are appended as JSON, one file per cell, so a driver can run cells in
separate processes (fresh XLA heap each) and accumulate.
"""

# The 512 placeholder devices MUST be configured before jax initializes —
# these lines are deliberately the first executable statements.  The tool is
# compile-only and pinned to the CPU platform, so on a TPU host it never
# claims the chip from the process that owns it.
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp                       # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P   # noqa: E402

from repro.configs.base import ModelConfig, ShapeConfig      # noqa: E402
from repro.configs.registry import (ARCHS, cell_is_live, get_arch,  # noqa: E402
                                    get_shape, live_cells)
from repro.launch.mesh import make_production_mesh            # noqa: E402
from repro.models.model_zoo import build_model                # noqa: E402
from repro.optim.adamw import AdamWConfig, init_opt_state     # noqa: E402
from repro.train.sharding import (make_batch_shardings,       # noqa: E402
                                  make_param_shardings, mesh_axes)
from repro.train.step import TrainState, make_train_step      # noqa: E402

# ----------------------------------------------------------------- specs


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def microbatches_for(arch: ModelConfig, shape: ShapeConfig, mesh) -> int:
    """Grad-accumulation depth: per-device microbatch of ~1 sample for the
    big models bounds saved activations."""
    if shape.kind != "train":
        return 1
    fsdp, _ = mesh_axes(mesh)
    n = 1
    for a in fsdp:
        n *= mesh.shape[a]
    return max(1, min(shape.global_batch // n, shape.microbatches * 2))


def input_specs(arch: ModelConfig, shape: ShapeConfig, mesh) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    S, B = shape.seq_len, shape.global_batch
    F = arch.frontend_len if arch.frontend else 0
    enc_len = arch.frontend_len if arch.family == "encdec" else 0
    d = jnp.bfloat16 if arch.dtype == "bfloat16" else jnp.float32

    if shape.kind == "train":
        M = microbatches_for(arch, shape, mesh)
        mb = B // M
        batch = {"tokens": _sds((M, mb, S - F), jnp.int32),
                 "labels": _sds((M, mb, S - F), jnp.int32)}
        if arch.frontend:
            batch["frontend"] = _sds((M, mb, F, arch.d_model), d)
        if arch.family == "encdec":
            batch["src_embeds"] = _sds((M, mb, enc_len, arch.d_model), d)
        return batch

    if shape.kind == "prefill":
        batch = {"tokens": _sds((B, S - F), jnp.int32)}
        if arch.frontend:
            batch["frontend"] = _sds((B, F, arch.d_model), d)
        if arch.family == "encdec":
            batch["src_embeds"] = _sds((B, enc_len, arch.d_model), d)
        return batch

    # decode: one new token against a seq_len-deep cache
    return {"tokens": _sds((B, 1), jnp.int32)}


# ----------------------------------------------------------- cache sharding

def decode_state_shardings(mesh, state_shapes):
    """KV caches shard: batch over (pod,data) when divisible, cache sequence
    axis over "model" (context parallelism); recurrent states shard their
    feature axis over "model"."""
    fsdp, tp = mesh_axes(mesh)
    n_fsdp = 1
    for a in fsdp:
        n_fsdp *= mesh.shape[a]

    tp_n = mesh.shape[tp] if tp else 1

    def one(path, leaf):
        pstr = "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in path)
        field = pstr.rsplit("/", 1)[-1].lstrip(".")
        nd = leaf.ndim
        if field == "positions" or nd == 0:
            return NamedSharding(mesh, P())

        def spec_for(core: tuple) -> P:
            """Right-align a core spec; leading scan-stack dims replicate,
            and every axis is divisibility-checked on its dimension."""
            lead = nd - len(core)
            if lead < 0:
                core = core[-nd:]
                lead = 0
            full = (None,) * lead + core
            out = []
            for i, a in enumerate(full):
                if a is None:
                    out.append(None)
                    continue
                n = n_fsdp if a == fsdp else tp_n
                out.append(a if leaf.shape[i] % n == 0 else None)
            return P(*out)

        b = fsdp if fsdp else None
        if field in ("k", "v"):          # KV cache (B, C, Hkv, hd)
            # context parallelism: cache sequence axis over "model"
            return NamedSharding(mesh, spec_for((b, tp, None, None)))
        if field == "ssm":               # (B, H, P, N) — heads over model
            return NamedSharding(mesh, spec_for((b, tp, None, None)))
        if field == "conv":              # (B, k-1, C) — channels over model
            return NamedSharding(mesh, spec_for((b, None, tp)))
        if field == "h":                 # rglru state (B, W)
            return NamedSharding(mesh, spec_for((b, tp)))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(one, state_shapes)


# ----------------------------------------------------------- HLO parsing

_COLL_RE = re.compile(
    r"(\w[\w.\-]*)\s*=\s*((?:\([^)]*\))|(?:[a-z0-9]+\[[^\]]*\]\S*))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for x in dims.split(","):
            if x:
                n *= int(x)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective kind (output-shape sizes)."""
    out: dict[str, int] = {"all-reduce": 0, "all-gather": 0,
                           "reduce-scatter": 0, "all-to-all": 0,
                           "collective-permute": 0}
    counts: dict[str, int] = {k: 0 for k in out}
    for m in _COLL_RE.finditer(hlo_text):
        shape_text, kind = m.group(2), m.group(3)
        out[kind] += _shape_bytes(shape_text)
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


# ----------------------------------------------------------- lowering


def lower_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
               opt_overrides: dict | None = None):
    """Lower one cell; returns (lowered, mesh, meta)."""
    arch = get_arch(arch_name)
    if opt_overrides:
        import dataclasses
        arch = dataclasses.replace(arch, **opt_overrides)
    shape = get_shape(shape_name)
    ok, why = cell_is_live(arch, shape)
    if not ok:
        raise SystemExit(f"cell skipped by assignment rule: {why}")

    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.models import pspec
    pspec.set_mesh(mesh)
    model = build_model(arch)
    key = jax.random.PRNGKey(0)

    params_shapes = jax.eval_shape(model.init, key)
    param_sh = make_param_shardings(mesh, params_shapes)
    batch = input_specs(arch, shape, mesh)
    meta = {"arch": arch_name, "shape": shape_name,
            "multi_pod": multi_pod, "mesh": dict(mesh.shape)}

    with mesh:
        if shape.kind == "train":
            opt_shapes = jax.eval_shape(init_opt_state, params_shapes)
            state_shapes = TrainState(params=params_shapes, opt=opt_shapes,
                                      step=_sds((), jnp.int32))
            state_sh = TrainState(
                params=param_sh,
                opt=type(opt_shapes)(
                    m=make_param_shardings(mesh, opt_shapes.m),
                    v=make_param_shardings(mesh, opt_shapes.v),
                    count=NamedSharding(mesh, P())),
                step=NamedSharding(mesh, P()))
            batch_sh = make_batch_shardings(mesh, batch, shape.global_batch,
                                            batch_axis=1)
            step_fn = make_train_step(model, AdamWConfig())
            meta["microbatches"] = jax.tree.leaves(batch)[0].shape[0]
            lowered = jax.jit(
                step_fn, in_shardings=(state_sh, batch_sh),
                donate_argnums=(0,)).lower(state_shapes, batch)
        elif shape.kind == "prefill":
            batch_sh = make_batch_shardings(mesh, batch, shape.global_batch)

            def prefill_fn(params, b):
                return model.prefill(params, b, max_len=shape.seq_len)

            # pin the output cache layout (context-parallel: sequence axis
            # over "model") — default GSPMD output shardings can come back
            # badly laid out (multi-GiB replication observed)
            out_shapes = jax.eval_shape(prefill_fn, params_shapes, batch)
            out_sh = (make_batch_shardings(mesh, out_shapes[0],
                                           shape.global_batch),
                      decode_state_shardings(mesh, out_shapes[1]))
            lowered = jax.jit(
                prefill_fn, in_shardings=(param_sh, batch_sh),
                out_shardings=out_sh,
            ).lower(params_shapes, batch)
        else:  # decode
            enc_len = arch.frontend_len if arch.family == "encdec" else 0
            state_shapes = jax.eval_shape(
                lambda: model.init_decode_state(shape.global_batch,
                                                shape.seq_len, enc_len))
            state_sh = decode_state_shardings(mesh, state_shapes)
            batch_sh = make_batch_shardings(mesh, batch, shape.global_batch)

            def decode_fn(params, st, tokens):
                return model.decode_step(params, st, tokens)

            out_shapes = jax.eval_shape(decode_fn, params_shapes,
                                        state_shapes, batch["tokens"])
            out_sh = (make_batch_shardings(mesh, out_shapes[0],
                                           shape.global_batch),
                      decode_state_shardings(mesh, out_shapes[1]))
            lowered = jax.jit(
                decode_fn,
                in_shardings=(param_sh, state_sh, batch_sh["tokens"]),
                out_shardings=out_sh,
                donate_argnums=(1,),
            ).lower(params_shapes, state_shapes, batch["tokens"])
    return lowered, mesh, meta


def run_cell(arch_name: str, shape_name: str, *, multi_pod: bool,
             out_dir: str, opt_overrides: dict | None = None,
             tag: str = "") -> dict:
    t0 = time.time()
    lowered, mesh, meta = lower_cell(arch_name, shape_name,
                                     multi_pod=multi_pod,
                                     opt_overrides=opt_overrides)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = {}
    try:
        ma = compiled.memory_analysis()
        for k in ("argument_size_in_bytes", "output_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes",
                  "alias_size_in_bytes"):
            mem[k] = int(getattr(ma, k, 0) or 0)
    except Exception as e:                      # pragma: no cover
        mem["error"] = str(e)

    cost = {}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        for k, v in ca.items():
            if k in ("flops", "bytes accessed", "transcendentals",
                     "optimal_seconds") or k.startswith("bytes accessed"):
                cost[k] = float(v)
    except Exception as e:                      # pragma: no cover
        cost["error"] = str(e)

    hlo_text = compiled.as_text()
    coll = collective_bytes(hlo_text)          # raw, once-per-program view
    from repro.launch.hlo_cost import analyze_hlo
    corrected = analyze_hlo(hlo_text)          # trip-count-corrected totals

    rec = {**meta, "tag": tag, "lower_s": round(t_lower, 2),
           "compile_s": round(t_compile, 2), "memory": mem, "cost": cost,
           "collectives": coll, "corrected": corrected}
    os.makedirs(out_dir, exist_ok=True)
    fname = f"{arch_name}__{shape_name}__{'multi' if multi_pod else 'single'}"
    if tag:
        fname += f"__{tag}"
    with open(os.path.join(out_dir, fname + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    cells = live_cells() if args.all else [(args.arch, args.shape)]
    for arch_name, shape_name in cells:
        try:
            rec = run_cell(arch_name, shape_name, multi_pod=args.multi_pod,
                           out_dir=args.out)
            print(f"OK  {arch_name} {shape_name} multi_pod={args.multi_pod} "
                  f"compile={rec['compile_s']}s "
                  f"flops={rec['cost'].get('flops', '?'):.3e} "
                  f"coll={rec['collectives']['total_bytes']/2**20:.1f}MiB")
            print("  memory:", rec["memory"])
        except SystemExit as e:
            print(f"SKIP {arch_name} {shape_name}: {e}")
        except Exception:
            print(f"FAIL {arch_name} {shape_name}")
            traceback.print_exc()


if __name__ == "__main__":
    main()
