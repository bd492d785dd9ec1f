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


def base_key(seed: int) -> jax.Array:
    s = int(seed) % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _leaf(key, name: str, shape, init: str, dtype) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(key, shape, F32)
    if init == "matrix":                        # fan-in: the contracted axis
        v = z * shape[-2] ** -0.5
    elif init == "embed":
        v = z * shape[-1] ** -0.5
    elif init == "scale":
        v = 1.0 + 0.05 * z
    elif init == "bias":
        v = 0.02 * z
    else:
        raise ValueError(f"leaf {name}: unknown init rule {init!r}")
    return v.astype(dtype)


def layer_weights(key, arch, m: dict, layer, kind: str) -> dict:
    """Leaves of decoder layer ``layer`` (may be traced), of kind ``kind``."""
    k = jax.random.fold_in(key, 1000 + layer)
    return {n: _leaf(k, n, *s) for n, s in arch.layer_spec(m, kind).items()}


def global_weights(key, arch, m: dict) -> dict:
    k = jax.random.fold_in(key, 1)
    return {n: _leaf(k, n, *s) for n, s in arch.global_spec(m).items()}


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


def program_params(model, arch, m: dict, seed: int):
    """The program's parameter tree filled with this benchmark's weights,
    made on the device in one jitted call.  Layer ``l`` of the program's
    stacking (pattern position ``pos`` of scan group ``g`` is ``g * period
    + pos``, then the unscanned rest) gets the leaves of the kind
    ``arch.layer_kinds(m)[l]``; every program leaf must be one of them, at
    its shape and dtype, and every one of them a program leaf."""
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    groups, rest = shapes["decoder"]["groups"], shapes["decoder"]["rest"]
    period = len(groups)
    n_groups = jax.tree.leaves(groups[0])[0].shape[0] if period else 0
    kinds = list(arch.layer_kinds(m))
    if n_groups * period + len(rest) != m["n_layers"] \
            or len(kinds) != m["n_layers"]:
        raise ValueError("program layer stacking does not cover n_layers")

    def placed(tree, spec, where):
        """(program path, benchmark leaf name) of each leaf of ``tree``."""
        out = []
        for path, leaf in _flat(tree):
            name = ".".join(path)
            if name not in spec:
                raise ValueError(f"program leaf {name} of {where} has no "
                                 f"benchmark weight")
            shape, _, dtype = spec[name]
            if tuple(leaf.shape[-len(shape):]) != shape \
                    or leaf.dtype != dtype:
                raise ValueError(f"program leaf {name} of {where} is "
                                 f"{leaf.shape} {leaf.dtype}, benchmark has "
                                 f"{shape} {jnp.dtype(dtype)}")
            out.append((path, name))
        missing = set(spec) - {name for _, name in out}
        if missing:
            raise ValueError(f"benchmark weights {sorted(missing)} of {where} "
                             f"have no program leaf")
        return out

    def build(key):
        out = _skeleton(shapes)
        glob = global_weights(key, arch, m)
        tops = {top: shapes[top] for top in ("embed", "final_norm", "head")}
        for path, name in placed(tops, arch.global_spec(m), "the globals"):
            _set(out, path, glob[name])
        for pos in range(period):
            ls = [g * period + pos for g in range(n_groups)]
            for l in ls:
                leaves = placed(groups[pos], arch.layer_spec(m, kinds[l]),
                                f"layer {l} ({kinds[l]})")
            layers = [layer_weights(key, arch, m, l, kinds[l]) for l in ls]
            for path, name in leaves:
                _set(out["decoder"]["groups"][pos], path,
                     jnp.stack([lw[name] for lw in layers]))
        for i in range(len(rest)):
            l = n_groups * period + i
            lw = layer_weights(key, arch, m, l, kinds[l])
            for path, name in placed(rest[i], arch.layer_spec(m, kinds[l]),
                                     f"layer {l} ({kinds[l]})"):
                _set(out["decoder"]["rest"][i], path, lw[name])
        return out

    return jax.jit(build)(base_key(seed))


def act_scale(arch, m: dict, seed: int, calib: dict, n_bits: int) -> float:
    """Activation quantization step of the digit-serial up-projection:
    ``max |x| / qmax`` over the normalized embeddings of a calibration
    prompt drawn from the seed (the up-projection's input is a normalized
    residual row), normalized by the architecture's own norm."""
    key = base_key(seed)

    @jax.jit
    def calibrate(key):
        g = global_weights(key, arch, m)
        toks = jax.random.randint(
            jax.random.fold_in(key, calib["seed_offset"]),
            (calib["tokens"],), 0, m["vocab_size"])
        x = g["embed.embedding"][toks].astype(F32)
        return jnp.max(jnp.abs(arch.norm(x, g, "final_norm", m)))

    qmax = float(2 ** (n_bits - 1) - 1)
    return float(calibrate(key)) / qmax
