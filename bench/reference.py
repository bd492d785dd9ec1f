"""Plain reference of the served models, and the comparison that decides
``correct``.

The reference is the decoder's full forward pass over a prompt and the
tokens the program served, in float32 at the highest matmul precision,
layer by layer from the benchmark's own weights (``weights.py``).  It
imports nothing of the program.  Where the configuration runs the MLP
up-projection digit-serially, the reference computes what that layer is
specified to compute: activations quantized to ``n_bits`` signed bits with
the calibrated step, truncated to the request's top ``n_planes`` digit
planes, times the weights, then ReLU.

The control is the same reference computed in int8, the step below the
configuration's bf16 that a later change would be tempted by: every weight
matrix rounded to int8 per output channel and every matmul input rounded
to int8 per row (W8A8, symmetric).  Where the configuration states 8-bit
activation digits for the up-projection, the control takes the step below
that too, for every request at its own budget: 4-bit digits (the same
calibrated range) and half the request's planes (8 -> 4, 6 -> 3, 4 -> 2),
int8 weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from weights import F32, base_key, global_weights, head_dim, layer_weights, norm

HIGHEST = jax.lax.Precision.HIGHEST
_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def int8_rows(w: jax.Array, axis: int) -> jax.Array:
    """Symmetric int8 rounding of ``w`` with one step per slice along
    ``axis`` reduced away (per output channel)."""
    s = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..T-1, halves convention.  x: (T, H, D)."""
    T, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def digit_truncate(x: jax.Array, step, n_bits: int, n_planes) -> jax.Array:
    """Signed ``n_bits`` quantization with step ``step``, keeping the top
    ``n_planes`` most-significant digit planes of each magnitude."""
    qmax = 2 ** (n_bits - 1) - 1
    q = jnp.clip(jnp.round(x / step), -qmax, qmax).astype(jnp.int32)
    drop = (n_bits - jnp.asarray(n_planes, jnp.int32))
    mag = (jnp.abs(q) >> drop) << drop
    return (jnp.sign(q) * mag).astype(F32) * step


def _layer(x, p, m, dslot, step, n_planes, control):
    T = x.shape[0]
    hd, H, Hkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    w = {k: v.astype(F32) for k, v in p.items()}
    if control:
        w = {k: int8_rows(v, 0) if v.ndim == 2 else v for k, v in w.items()}
    # the control's matmul inputs are int8 per row
    act = (lambda a: int8_rows(a, -1)) if control else (lambda a: a)
    h = act(norm(x, w, "norm1", m))

    def proj(name):
        y = mm(h, w[f"attn.{name}.w"])
        return y + w[f"attn.{name}.b"] if f"attn.{name}.b" in w else y

    q = rope(proj("wq").reshape(T, H, hd), m["rope_theta"])
    k = rope(proj("wk").reshape(T, Hkv, hd), m["rope_theta"])
    v = proj("wv").reshape(T, Hkv, hd)
    k, v = jnp.repeat(k, H // Hkv, 1), jnp.repeat(v, H // Hkv, 1)
    s = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("hqk,khd->qhd", a, v, precision=HIGHEST)
    x = x + mm(act(o.reshape(T, H * hd)), w["attn.wo.w"])

    h = norm(x, w, "norm2", m)
    if dslot is not None:
        bits = dslot["n_bits"]
        if control:     # half the digits and half the planes, same range
            ctl = bits // 2
            step = step * (2 ** (bits - 1) - 1) / (2 ** (ctl - 1) - 1)
            bits, n_planes = ctl, (n_planes + 1) // 2
        up = jax.nn.relu(mm(digit_truncate(h, step, bits, n_planes),
                            w["mlp.up.w"]))
    else:
        h = act(h)
        up = _ACTS[m["act"]](mm(h, w["mlp.up.w"]))
        if m["glu"]:
            up = _ACTS[m["act"]](mm(h, w["mlp.gate.w"])) * mm(h, w["mlp.up.w"])
    return x + mm(act(up), w["mlp.down.w"])


@functools.lru_cache(maxsize=None)
def _programs(mkey, dkey, control: bool):
    m, dslot = dict(mkey), (None if dkey is None else dict(dkey))

    @jax.jit
    def embed(key, tokens):
        g = global_weights(key, m)
        e = g["embed.embedding"].astype(F32)
        if control:
            e = int8_rows(e, 1)
        return e[tokens]

    @jax.jit
    def layer(key, x, l, step, n_planes):
        return _layer(x, layer_weights(key, m, l), m, dslot, step, n_planes,
                      control)

    @jax.jit
    def logits(key, x):
        g = {k: v.astype(F32) for k, v in global_weights(key, m).items()}
        h = norm(x, g, "final_norm", m)
        if control:
            h = int8_rows(h, -1)
        if m["tie_embeddings"]:
            e = g["embed.embedding"]
            return mm(h, (int8_rows(e, 1) if control else e).T)
        w = g["head.w"]
        return mm(h, int8_rows(w, 0) if control else w)

    return embed, layer, logits


def forward(m: dict, dslot: dict | None, seed: int, tokens: np.ndarray,
            pad_to: int, step: float = 1.0, n_planes: int = 8,
            control: bool = False) -> np.ndarray:
    """Logits (len(tokens), vocab) of the reference (or its control)."""
    T = len(tokens)
    toks = np.zeros((max(pad_to, T),), np.int32)
    toks[:T] = tokens
    mkey = tuple(sorted((k, v) for k, v in m.items()
                        if not isinstance(v, (dict, list))))
    dkey = None if dslot is None else tuple(sorted(dslot.items()))
    embed, layer, logits = _programs(mkey, dkey, control)
    key = base_key(seed)
    x = embed(key, jnp.asarray(toks))
    for l in range(m["n_layers"]):
        x = layer(key, x, jnp.int32(l), jnp.float32(step),
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


def compare(m: dict, dslot: dict | None, seed: int, samples: list,
            pad_to: int, step: float, with_control: bool = False) -> dict:
    """Reference readings over ``samples`` = [(prompt, served, n_planes)]."""
    gaps, ctl, agree = [], [], 0
    for prompt, served, npl in samples:
        ids = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        ref = forward(m, dslot, seed, ids, pad_to, step, npl)
        g = served_gaps(ref, len(prompt), served)
        gaps.append(g)
        agree += int(np.sum(g == 0))
        if with_control:
            c = forward(m, dslot, seed, ids, pad_to, step, npl, control=True)
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
