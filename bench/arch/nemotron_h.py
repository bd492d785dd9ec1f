"""NVIDIA Nemotron-H (``nemotron_h``): one mixer a layer, Mamba-2 (``ssm``),
a sparse mixture of experts (``moe_only``) or grouped-query attention
(``attn_only``), in the configuration's ``block_pattern``.

Every layer is ``x + mixer(RMSNorm(x))`` (epsilon ``norm_eps``), and the
logits are an untied head over the final RMSNorm.  The mixers, as this
reference computes them in float32 with every matmul at the highest
precision:

- ``ssm``: ``in_proj`` to [z | xBC | dt]; a causal depthwise conv with bias
  over xBC, then SiLU, split into x (H heads of P), B and C (G groups of N;
  head h reads group h // (H / G)); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; the SSD recurrence ``h_t = exp(dt A) h_{t-1} + dt
  x_t B_t``, ``y_t = C_t h_t + D x_t``, run position by position; an RMSNorm
  of ``y * silu(z)`` over each group of H P / G channels, times its weight;
  ``out_proj``.
- ``moe_only``: sigmoid scores of a router over all ``n_experts``; the
  ``top_k`` of the scores plus ``e_score_correction_bias`` are chosen,
  weighted by their scores alone, renormalized and times ``moe_scale``.
  Only the experts this chip holds (``expert_offset`` on, ``experts_held``
  of them; the configuration's ``deployment``) add their part: each one's
  ``down(relu(up x)^2)`` over every position, times its routed weight there
  (zero where it was not chosen).  A ``relu2`` shared expert adds to every
  position.
- ``attn_only``: causal grouped-query attention with no position
  embedding.

The control is the same reference in int8: every weight matrix rounded per
output channel (each expert's too) and every matmul input per row (W8A8).
``decode_step_bytes`` counts what one pooled decode step has to move.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from arch import dense
from reference import HIGHEST, int8_rows, mm
from weights import F32

global_spec, embed = dense.global_spec, dense.embed


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def ssm_dims(m: dict) -> tuple[int, int, int, int, int]:
    """(channels H P, heads H, head dim P, state N, groups G)."""
    P, H = m["ssm_headdim"], m["ssm_heads"]
    return H * P, H, P, m["ssm_state"], m["ssm_groups"]


# ------------------------------------------------------------- leaves

def layer_kinds(m: dict) -> list[str]:
    pat = m["block_pattern"]
    return [pat[i % len(pat)] for i in range(m["n_layers"])]


def layer_spec(m: dict, kind: str) -> dict:
    """name -> (shape, init, dtype) of one layer's leaves."""
    d, dt = m["d_model"], jnp.dtype(m["dtype"])
    spec = {"norm.scale": ((d,), "scale", F32)}
    if kind == "ssm":
        di, H, _, N, G = ssm_dims(m)
        conv = di + 2 * G * N
        spec.update({
            "mixer.w_in": ((d, di + conv + H), "matrix", dt),
            "mixer.conv_w": ((m["ssm_conv"], conv), "matrix", dt),
            "mixer.conv_b": ((conv,), "bias", dt),
            "mixer.A_log": ((H,), "scale", F32),
            "mixer.D_skip": ((H,), "scale", F32),
            "mixer.dt_bias": ((H,), "bias", F32),
            "mixer.norm_scale": ((di,), "scale", F32),
            "mixer.w_out": ((di, d), "matrix", dt)})
    elif kind == "moe_only":
        e, el = m["n_experts"], m["experts_held"]
        f, fs = m["d_ff"], m["moe_shared_ff"]
        spec.update({
            "moe.router": ((d, e), "matrix", F32),
            "moe.e_score_correction_bias": ((e,), "bias", F32),
            "moe.up": ((el, d, f), "matrix", dt),
            "moe.down": ((el, f, d), "matrix", dt),
            "moe.shared.up.w": ((d, fs), "matrix", dt),
            "moe.shared.down.w": ((fs, d), "matrix", dt)})
    elif kind == "attn_only":
        hq = m["n_heads"] * m["head_dim"]
        hkv = m["n_kv_heads"] * m["head_dim"]
        spec.update({"attn.wq.w": ((d, hq), "matrix", dt),
                     "attn.wk.w": ((d, hkv), "matrix", dt),
                     "attn.wv.w": ((d, hkv), "matrix", dt),
                     "attn.wo.w": ((hq, d), "matrix", dt)})
    else:
        raise ValueError(f"nemotron_h has no layer kind {kind!r}")
    return spec


# ------------------------------------------------------------- reference

def norm(x: jax.Array, p: dict, name: str, m: dict) -> jax.Array:
    """RMSNorm in f32 with the configuration's epsilon."""
    r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m["norm_eps"])
    return x * r * p[f"{name}.scale"].astype(F32)


def _leaves(p: dict, control: bool) -> dict:
    """f32 leaves; in the control every matrix, and each expert's, int8
    per output channel."""
    w = dense.f32_leaves(p, control)
    if control:
        w = {k: int8_rows(v, 1) if v.ndim == 3 else v for k, v in w.items()}
    return w


def _ein(spec, *args):
    return jnp.einsum(spec, *args, precision=HIGHEST)


def ssm_layer(x, p, m, dslot, step, n_planes, control):
    """One Mamba-2 layer, the recurrence run position by position."""
    w, act = _leaves(p, control), dense.row_rounding(control)
    T = x.shape[0]
    di, H, P, N, G = ssm_dims(m)
    k = m["ssm_conv"]
    zxbcdt = mm(act(norm(x, w, "norm", m)), w["mixer.w_in"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    xp = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), F32), xbc])
    conv = sum(xp[j:j + T] * w["mixer.conv_w"][j] for j in range(k))
    xbc = jax.nn.silu(conv + w["mixer.conv_b"])
    xs, B, C = jnp.split(xbc, [di, di + G * N], axis=-1)
    xs = xs.reshape(T, H, P)
    group = jnp.arange(H) // (H // G)
    B = B.reshape(T, G, N)[:, group]                     # (T, H, N)
    C = C.reshape(T, G, N)[:, group]
    dt = jax.nn.softplus(dt + w["mixer.dt_bias"])        # (T, H)
    A = -jnp.exp(w["mixer.A_log"])

    def one(h, t):
        xt, dtt, bt, ct = t
        h = jnp.exp(dtt * A)[:, None, None] * h \
            + _ein("h,hp,hn->hpn", dtt, xt, bt)
        return h, _ein("hpn,hn->hp", h, ct)

    _, y = jax.lax.scan(one, jnp.zeros((H, P, N), F32), (xs, dt, B, C))
    y = y + w["mixer.D_skip"][:, None] * xs
    g = (y * jax.nn.silu(z).reshape(T, H, P)).reshape(T, G, di // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                          + m["norm_eps"])
    g = g.reshape(T, di) * w["mixer.norm_scale"]
    return x + mm(act(g), w["mixer.w_out"])


def moe_layer(x, p, m, dslot, step, n_planes, control):
    """One MoE layer: this chip's held experts and the shared expert."""
    w, act = _leaves(p, control), dense.row_rounding(control)
    h = act(norm(x, w, "norm", m))
    scores = jax.nn.sigmoid(mm(h, w["moe.router"]))
    _, idx = jax.lax.top_k(scores + w["moe.e_score_correction_bias"],
                           m["top_k"])
    top = jnp.take_along_axis(scores, idx, -1)
    top = top / jnp.sum(top, -1, keepdims=True) * m["moe_scale"]
    y = mm(act(relu2(mm(h, w["moe.shared.up.w"]))), w["moe.shared.down.w"])
    for e in range(m["experts_held"]):
        share = jnp.sum(jnp.where(idx == m["expert_offset"] + e, top, 0.0),
                        -1)
        inner = relu2(mm(h, w["moe.up"][e]))
        y = y + share[:, None] * mm(act(inner), w["moe.down"][e])
    return x + y


def attn_layer(x, p, m, dslot, step, n_planes, control):
    """One attention layer: causal, grouped KV heads, no positions."""
    w, act = _leaves(p, control), dense.row_rounding(control)
    T, hd = x.shape[0], m["head_dim"]
    H, Hkv = m["n_heads"], m["n_kv_heads"]
    h = act(norm(x, w, "norm", m))
    q = mm(h, w["attn.wq.w"]).reshape(T, H, hd)
    k = jnp.repeat(mm(h, w["attn.wk.w"]).reshape(T, Hkv, hd), H // Hkv, 1)
    v = jnp.repeat(mm(h, w["attn.wv.w"]).reshape(T, Hkv, hd), H // Hkv, 1)
    s = _ein("qhd,khd->hqk", q, k) * hd ** -0.5
    causal = jnp.tril(jnp.ones((T, T), bool))
    a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = _ein("hqk,khd->qhd", a, v).reshape(T, H * hd)
    return x + mm(act(o), w["attn.wo.w"])


LAYERS = {"ssm": ssm_layer, "moe_only": moe_layer, "attn_only": attn_layer}


def logits(g: dict, x: jax.Array, m: dict, control: bool) -> jax.Array:
    g = {k: v.astype(F32) for k, v in g.items()}
    h = norm(x, g, "final_norm", m)
    if control:
        h = int8_rows(h, -1)
    return mm(h, int8_rows(g["head.w"], 0) if control else g["head.w"])


# ------------------------------------------------------------- operations

def _counts(m: dict) -> dict:
    kinds = layer_kinds(m)
    return {k: kinds.count(k) for k in LAYERS}


def _layer_params(m: dict) -> float:
    """Matmul parameters one token passes through in all layers, the held
    experts at their expected share ``top_k * experts_held / n_experts``."""
    d, n = m["d_model"], _counts(m)
    di, H, _, N, G = ssm_dims(m)
    ssm = d * (2 * di + 2 * G * N + H) + di * d
    share = m["top_k"] * m["experts_held"] / m["n_experts"]
    moe = d * m["n_experts"] + share * 2 * d * m["d_ff"] \
        + 2 * d * m["moe_shared_ff"]
    attn = d * m["head_dim"] * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    return n["ssm"] * ssm + n["moe_only"] * moe + n["attn_only"] * attn


def _ssm_token_flops(m: dict) -> float:
    """The conv and the recurrence of one token in all Mamba-2 layers:
    decay, input and update of the (H, P, N) state, its read-out by C."""
    di, H, P, N, G = ssm_dims(m)
    conv = 2 * m["ssm_conv"] * (di + 2 * G * N)
    return _counts(m)["ssm"] * (conv + 6.0 * H * P * N)


def _attention_flops(m: dict, pairs: float) -> float:
    return 4.0 * _counts(m)["attn_only"] * pairs * m["n_heads"] \
        * m["head_dim"]


def decode_token_flops(m: dict, ctx: int) -> float:
    """One generated token whose query sees ``ctx`` keys (itself included)."""
    return 2.0 * (_layer_params(m) + m["d_model"] * m["vocab_size"]) \
        + _ssm_token_flops(m) + _attention_flops(m, ctx)


def prefill_flops(m: dict, prompt_len: int) -> float:
    """A whole prompt through the layers, causal attention, and the head
    once (only the last position's logits are needed)."""
    pairs = prompt_len * (prompt_len + 1) / 2.0
    return (2.0 * _layer_params(m) + _ssm_token_flops(m)) * prompt_len \
        + _attention_flops(m, pairs) + 2.0 * m["d_model"] * m["vocab_size"]


def _nbytes(spec: dict) -> float:
    total = 0.0
    for shape, _, dtype in spec.values():
        n = 1
        for s in shape:
            n *= s
        total += n * jnp.dtype(dtype).itemsize
    return total


def decode_step_bytes(m: dict, n_slots: float, ctx: float) -> float:
    """Bytes one pooled decode step of ``n_slots`` sequences at a live
    context of ``ctx`` tokens has to move, each once: every layer's weights
    (the held experts whole) and the head, the f32 SSM states read and
    written, the conv tails read and written in the model's dtype, the live
    KV read and the new token's written, the token embeddings read and the
    logits written.  The empty part of a KV ring is not counted."""
    item = jnp.dtype(m["dtype"]).itemsize
    d, n = m["d_model"], _counts(m)
    di, H, P, N, G = ssm_dims(m)
    weights = sum(_nbytes(layer_spec(m, k)) for k in layer_kinds(m)) \
        + _nbytes({k: v for k, v in global_spec(m).items()
                   if k != "embed.embedding"})
    state = n["ssm"] * n_slots * 2 * (
        H * P * N * 4 + (m["ssm_conv"] - 1) * (di + 2 * G * N) * item)
    kv = n["attn_only"] * n_slots * (ctx + 1) * 2 * m["n_kv_heads"] \
        * m["head_dim"] * item
    io = n_slots * (d + m["vocab_size"]) * item
    return weights + state + kv + io
