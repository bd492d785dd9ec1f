"""Plain reference of the served models, and the comparison that decides
``correct``.

The reference is the decoder's full forward pass over a prompt and the
tokens the program served, in float32 at the highest matmul precision,
layer by layer from the benchmark's own weights (``weights.py``).  It
imports nothing of the program.  What a layer computes is the
configuration's architecture module's (``arch/<name>.py``: one function per
layer kind, the embedding and the logits); this file runs it, one jitted
program per layer kind, and holds the arithmetic every module shares: f32
matmuls at ``HIGHEST``, the int8 rounding of the control, and the
digit-serial truncation.

The control is the same reference computed in int8, the step below the
configuration's bf16 that a later change would be tempted by (each module
says how its layers take that step; ``int8_rows`` rounds symmetrically,
one step per output channel or per row).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import F32, base_key, global_weights, layer_weights

HIGHEST = jax.lax.Precision.HIGHEST


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def int8_rows(w: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 rounding of ``w`` with one step per slice along
    ``axis`` reduced away (per output channel)."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def digit_truncate(x: jax.Array, step, n_bits: int, n_planes) -> jax.Array:
    """Signed ``n_bits`` quantization with step ``step``, keeping the top
    ``n_planes`` most-significant digit planes of each magnitude."""
    qmax = 2 ** (n_bits - 1) - 1
    q = jnp.clip(jnp.round(x / step), -qmax, qmax).astype(jnp.int32)
    drop = (n_bits - jnp.asarray(n_planes, jnp.int32))
    mag = (jnp.abs(q) >> drop) << drop
    return (jnp.sign(q) * mag).astype(F32) * step


def _hashable(v):
    """Lists as tuples, so a whole model dict keys the program cache."""
    return tuple(map(_hashable, v)) if isinstance(v, (list, tuple)) else v


@functools.lru_cache(maxsize=None)
def _programs(arch, mkey, dkey, control: bool):
    m, dslot = dict(mkey), (None if dkey is None else dict(dkey))

    @jax.jit
    def embed(key, tokens):
        return arch.embed(global_weights(key, arch, m), tokens, m, control)

    def layer_program(kind):
        fn = arch.LAYERS[kind]

        @jax.jit
        def layer(key, x, l, step, n_planes):
            return fn(x, layer_weights(key, arch, m, l, kind), m, dslot,
                      step, n_planes, control)
        return layer

    @jax.jit
    def logits(key, x):
        return arch.logits(global_weights(key, arch, m), x, m, control)

    layers = {kind: layer_program(kind) for kind in set(arch.layer_kinds(m))}
    return embed, layers, logits


def forward(arch, m: dict, dslot: dict | None, seed: int, tokens: np.ndarray,
            pad_to: int, step: float = 1.0, n_planes: int = 8,
            control: bool = False) -> np.ndarray:
    """Logits (len(tokens), vocab) of the reference (or its control) of
    the architecture module ``arch``."""
    T = len(tokens)
    toks = np.zeros((max(pad_to, T),), np.int32)
    toks[:T] = tokens
    mkey = tuple(sorted((k, _hashable(v)) for k, v in m.items()))
    dkey = None if dslot is None else tuple(sorted(dslot.items()))
    embed, layers, logits = _programs(arch, mkey, dkey, control)
    key = base_key(seed)
    x = embed(key, jnp.asarray(toks))
    for l, kind in enumerate(arch.layer_kinds(m)):
        x = layers[kind](key, x, jnp.int32(l), jnp.float32(step),
                         jnp.int32(n_planes))
    return np.asarray(logits(key, x))[:T]


def served_gaps(ref_logits: np.ndarray, prompt_len: int,
                served: list[int]) -> np.ndarray:
    """For each served token, how far its reference logit lies below the
    reference's best at that position."""
    rows = ref_logits[prompt_len - 1: prompt_len - 1 + len(served)]
    best = rows.max(axis=-1)
    return best - rows[np.arange(len(served)), np.asarray(served)]


def control_gaps(ref_logits: np.ndarray, ctl_logits: np.ndarray,
                 prompt_len: int, n: int) -> np.ndarray:
    """The gap, under the reference, of the token the control puts first."""
    sl = slice(prompt_len - 1, prompt_len - 1 + n)
    top = ctl_logits[sl].argmax(axis=-1)
    rows = ref_logits[sl]
    return rows.max(axis=-1) - rows[np.arange(n), top]


def compare(arch, m: dict, dslot: dict | None, seed: int, samples: list,
            pad_to: int, step: float, with_control: bool = False) -> dict:
    """Reference readings over ``samples`` = [(prompt, served, n_planes)]."""
    gaps, ctl, agree = [], [], 0
    for prompt, served, npl in samples:
        ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        ref = forward(arch, m, dslot, seed, ids, pad_to, step, npl)
        g = served_gaps(ref, len(prompt), served)
        gaps.append(g)
        agree += int(np.sum(g == 0))
        if with_control:
            c = forward(arch, m, dslot, seed, ids, pad_to, step, npl,
                        control=True)
            ctl.append(control_gaps(ref, c, len(prompt), len(served)))
    allg = np.concatenate(gaps) if gaps else np.zeros(0)
    by_planes = {}
    for i, (_, _, npl) in enumerate(samples):
        b = by_planes.setdefault(str(npl), {"tokens": 0, "gap_sum": 0.0,
                                            "control_gap_sum": 0.0})
        b["tokens"] += int(gaps[i].size)
        b["gap_sum"] += float(gaps[i].sum())
        if with_control:
            b["control_gap_sum"] += float(ctl[i].sum())
    for b in by_planes.values():     # mean gaps of each plane budget
        n = max(1, b["tokens"])
        b["mean_logit_gap"] = b.pop("gap_sum") / n
        ctl_sum = b.pop("control_gap_sum")
        if with_control:
            b["control_mean_logit_gap"] = ctl_sum / n
    out = {"tokens": int(allg.size), "by_planes": by_planes,
           "mean_logit_gap": float(allg.mean()) if allg.size else 1e9,
           "max_logit_gap": float(allg.max()) if allg.size else 1e9,
           "top1_agreement": agree / max(1, allg.size)}
    if with_control:
        c = np.concatenate(ctl) if ctl else np.zeros(0)
        out["control_mean_logit_gap"] = float(c.mean()) if c.size else 0.0
        out["control_max_logit_gap"] = float(c.max()) if c.size else 0.0
        out["control_top1_agreement"] = float(np.mean(c == 0)) \
            if c.size else 0.0
    return out
