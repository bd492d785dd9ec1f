"""The benchmark's own weights, made on the device from ``--seed``.

Every leaf is a function of (seed, layer, leaf name) alone, so the plain
reference can regenerate any one layer by itself and never reads what the
program holds.  ``program_params`` lays the same values out in the
program's parameter tree (scan groups stacked along a leading axis) in one
jitted call, in the dtype they are served in.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

F32 = jnp.float32


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def base_key(seed: int) -> jax.Array:
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _norm_spec(m: dict, name: str) -> dict:
    d = m["d_model"]
    if m["norm"] == "layernorm":
        return {f"{name}.scale": ((d,), "scale"),
                f"{name}.bias": ((d,), "lnbias")}
    if m["norm"] == "rmsnorm":
        return {f"{name}.scale": ((d,), "scale")}
    return {}                                   # non-parametric


def layer_spec(m: dict) -> dict:
    """name -> (shape, kind) of one decoder layer's leaves."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    hq, hkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    spec = {**_norm_spec(m, "norm1"), **_norm_spec(m, "norm2"),
            "attn.wq.w": ((d, hq), "matrix"), "attn.wk.w": ((d, hkv), "matrix"),
            "attn.wv.w": ((d, hkv), "matrix"), "attn.wo.w": ((hq, d), "matrix"),
            "mlp.up.w": ((d, f), "matrix"), "mlp.down.w": ((f, d), "matrix")}
    if m["glu"]:
        spec["mlp.gate.w"] = ((d, f), "matrix")
    if m["qkv_bias"]:
        spec.update({"attn.wq.b": ((hq,), "bias"),
                     "attn.wk.b": ((hkv,), "bias"),
                     "attn.wv.b": ((hkv,), "bias")})
    return spec


def global_spec(m: dict) -> dict:
    spec = {"embed.embedding": ((m["vocab_size"], m["d_model"]), "embed"),
            **_norm_spec(m, "final_norm")}
    if not m["tie_embeddings"]:
        spec["head.w"] = ((m["d_model"], m["vocab_size"]), "matrix")
    return spec


def leaf_dtype(m: dict, kind: str):
    # norm parameters are kept in f32, everything else in the served dtype
    return F32 if kind in ("scale", "lnbias") else jnp.dtype(m["dtype"])


def _leaf(key, name: str, shape, kind: str, m: dict) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(key, shape, F32)
    if kind == "matrix":
        v = z * shape[0] ** -0.5
    elif kind == "embed":
        v = z * shape[1] ** -0.5
    elif kind == "scale":
        v = 1.0 + 0.05 * z
    else:                                       # "bias", "lnbias"
        v = 0.02 * z
    return v.astype(leaf_dtype(m, kind))


def layer_weights(key, m: dict, layer) -> dict:
    """Leaves of decoder layer ``layer`` (may be traced)."""
    k = jax.random.fold_in(key, 1000 + layer)
    return {n: _leaf(k, n, s, kind, m) for n, (s, kind)
            in layer_spec(m).items()}


def global_weights(key, m: dict) -> dict:
    k = jax.random.fold_in(key, 1)
    return {n: _leaf(k, n, s, kind, m) for n, (s, kind)
            in global_spec(m).items()}


def _flat(tree, prefix=()):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flat(v, prefix + (k,))
    else:
        yield prefix, tree


def _set(tree, path, value):
    node = tree
    for k in path[:-1]:
        node = node[k]
    node[path[-1]] = value


def _skeleton(tree):
    if isinstance(tree, dict):
        return {k: _skeleton(v) for k, v in tree.items()}
    if isinstance(tree, list):
        return [_skeleton(v) for v in tree]
    return None


def program_params(model, m: dict, seed: int):
    """The program's parameter tree filled with this benchmark's weights,
    made on the device in one jitted call."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    groups, rest = shapes["decoder"]["groups"], shapes["decoder"]["rest"]
    period = len(groups)
    n_groups = jax.tree.leaves(groups[0])[0].shape[0] if period else 0
    if n_groups * period + len(rest) != m["n_layers"]:
        raise ValueError("program layer stacking does not cover n_layers")
    lspec, gspec = layer_spec(m), global_spec(m)

    def check(path, leaf, spec):
        name = ".".join(path)
        if name not in spec:
            raise ValueError(f"program leaf {name} has no benchmark weight")
        shape, kind = spec[name]
        if tuple(leaf.shape[-len(shape):]) != shape \
                or leaf.dtype != leaf_dtype(m, kind):
            raise ValueError(f"program leaf {name} is {leaf.shape} "
                             f"{leaf.dtype}, benchmark has {shape}")
        return name

    def build(key):
        out = _skeleton(shapes)
        glob = global_weights(key, m)
        for top in ("embed", "final_norm", "head"):
            for path, leaf in _flat(shapes[top], (top,)):
                _set(out, path, glob[check(path, leaf, gspec)])
        for pos in range(period):
            layers = [layer_weights(key, m, g * period + pos)
                      for g in range(n_groups)]
            for path, leaf in _flat(groups[pos]):
                name = check(path, leaf, lspec)
                _set(out["decoder"]["groups"][pos], path,
                     jnp.stack([lw[name] for lw in layers]))
        for i in range(len(rest)):
            lw = layer_weights(key, m, n_groups * period + i)
            for path, leaf in _flat(rest[i]):
                _set(out["decoder"]["rest"][i], path,
                     lw[check(path, leaf, lspec)])
        return out

    return jax.jit(build)(base_key(seed))


def norm(x: jax.Array, p: dict, name: str, m: dict) -> jax.Array:
    """The configuration's pre-norm in f32 (eps 1e-6, as the program)."""
    if m["norm"] == "rmsnorm":
        r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        return x * r * p[f"{name}.scale"].astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(jnp.var(x, -1, keepdims=True) + 1e-6)
    if m["norm"] == "layernorm":
        out = out * p[f"{name}.scale"].astype(F32) \
            + p[f"{name}.bias"].astype(F32)
    return out


def act_scale(m: dict, seed: int, calib: dict, n_bits: int) -> float:
    """Activation quantization step of the digit-serial up-projection:
    ``max |x| / qmax`` over the normalized embeddings of a calibration
    prompt drawn from the seed (the up-projection's input is a normalized
    residual row)."""
    key = base_key(seed)

    @jax.jit
    def calibrate(key):
        g = global_weights(key, m)
        toks = jax.random.randint(
            jax.random.fold_in(key, calib["seed_offset"]),
            (calib["tokens"],), 0, m["vocab_size"])
        x = g["embed.embedding"][toks].astype(F32)
        return jnp.max(jnp.abs(norm(x, g, "final_norm", m)))

    qmax = float(2 ** (n_bits - 1) - 1)
    return float(calibrate(key)) / qmax
