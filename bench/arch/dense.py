"""The dense decoder: self-attention and an MLP in every layer, the
program's ``attn`` layer kind, as ``opt-1.3b`` and ``olmo-1b`` serve it.

An architecture module tells the architecture-blind harness what one
configuration's layers hold (``layer_kinds``, ``layer_spec``,
``global_spec``), how its plain reference computes them (``LAYERS``,
``embed``, ``logits``, ``norm``) and how many model operations a token
needs (``decode_token_flops``, ``prefill_flops``).  ``PERF.md``, "Adding a
configuration", states the contract.

The reference is float32 at the highest matmul precision, from the
benchmark's own weights, and imports nothing of the program.  Where the
configuration runs the MLP up-projection digit-serially, it computes what
that layer is specified to compute: activations quantized to ``n_bits``
signed bits with the calibrated step, truncated to the request's top
``n_planes`` digit planes, times the weights, then ReLU.  Its control
(``control=True``) is the same layer in int8: every weight matrix rounded
per output channel and every matmul input per row (W8A8, symmetric); the
digit-serial up-projection takes the step below its digits too, 4-bit
digits over the same range and half the request's planes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import HIGHEST, digit_truncate, int8_rows, mm
from weights import F32

_ACTS = {"relu": jax.nn.relu, "silu": jax.nn.silu, "gelu": jax.nn.gelu}
EPS = 1e-6          # the program's fixed norm epsilon


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


# ------------------------------------------------------------- leaves

def layer_kinds(m: dict) -> list[str]:
    return ["attn"] * m["n_layers"]


def _norm_spec(m: dict, name: str) -> dict:
    d = m["d_model"]
    if m["norm"] == "layernorm":
        return {f"{name}.scale": ((d,), "scale", F32),
                f"{name}.bias": ((d,), "bias", F32)}
    if m["norm"] == "rmsnorm":
        return {f"{name}.scale": ((d,), "scale", F32)}
    return {}                                   # non-parametric


def layer_spec(m: dict, kind: str) -> dict:
    """name -> (shape, init, dtype) of one decoder layer's leaves; norm
    parameters in f32, everything else in the served dtype."""
    d, f, hd = m["d_model"], m["d_ff"], head_dim(m)
    hq, hkv = m["n_heads"] * hd, m["n_kv_heads"] * hd
    dt = jnp.dtype(m["dtype"])
    spec = {**_norm_spec(m, "norm1"), **_norm_spec(m, "norm2"),
            "attn.wq.w": ((d, hq), "matrix", dt),
            "attn.wk.w": ((d, hkv), "matrix", dt),
            "attn.wv.w": ((d, hkv), "matrix", dt),
            "attn.wo.w": ((hq, d), "matrix", dt),
            "mlp.up.w": ((d, f), "matrix", dt),
            "mlp.down.w": ((f, d), "matrix", dt)}
    if m["glu"]:
        spec["mlp.gate.w"] = ((d, f), "matrix", dt)
    if m["qkv_bias"]:
        spec.update({"attn.wq.b": ((hq,), "bias", dt),
                     "attn.wk.b": ((hkv,), "bias", dt),
                     "attn.wv.b": ((hkv,), "bias", dt)})
    return spec


def global_spec(m: dict) -> dict:
    dt = jnp.dtype(m["dtype"])
    spec = {"embed.embedding": ((m["vocab_size"], m["d_model"]), "embed",
                                dt),
            **_norm_spec(m, "final_norm")}
    if not m["tie_embeddings"]:
        spec["head.w"] = ((m["d_model"], m["vocab_size"]), "matrix", dt)
    return spec


# ------------------------------------------------------------- reference

def norm(x: jax.Array, p: dict, name: str, m: dict) -> jax.Array:
    """The configuration's pre-norm in f32 (eps 1e-6, as the program)."""
    if m["norm"] == "rmsnorm":
        r = jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS)
        return x * r * p[f"{name}.scale"].astype(F32)
    mu = jnp.mean(x, -1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(jnp.var(x, -1, keepdims=True) + EPS)
    if m["norm"] == "layernorm":
        out = out * p[f"{name}.scale"].astype(F32) \
            + p[f"{name}.bias"].astype(F32)
    return out


def rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary positions 0..T-1, halves convention.  x: (T, H, D)."""
    T, _, D = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = jnp.arange(T, dtype=F32)[:, None, None] * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def f32_leaves(p: dict, control: bool) -> dict:
    """A layer's leaves in f32; in the control, every matrix rounded to
    int8 per output channel."""
    w = {k: v.astype(F32) for k, v in p.items()}
    if control:
        w = {k: int8_rows(v, 0) if v.ndim == 2 else v for k, v in w.items()}
    return w


def row_rounding(control: bool):
    """The control's matmul inputs are int8 per row."""
    return (lambda a: int8_rows(a, -1)) if control else (lambda a: a)


def attention(x, w, m, control):
    """The residual stream after the layer's causal self-attention (rotary
    positions, grouped KV heads, q/k/v biases where given)."""
    T = x.shape[0]
    hd, H, Hkv = head_dim(m), m["n_heads"], m["n_kv_heads"]
    act = row_rounding(control)
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
    return x + mm(act(o.reshape(T, H * hd)), w["attn.wo.w"])


def attn_layer(x, p, m, dslot, step, n_planes, control):
    """One ``attn`` layer: attention, then the MLP (digit-serial
    up-projection where ``dslot`` is given)."""
    w = f32_leaves(p, control)
    act = row_rounding(control)
    x = attention(x, w, m, control)

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


LAYERS = {"attn": attn_layer}


def embed(g: dict, tokens: jax.Array, m: dict, control: bool) -> jax.Array:
    e = g["embed.embedding"].astype(F32)
    if control:
        e = int8_rows(e, 1)
    return e[tokens]


def logits(g: dict, x: jax.Array, m: dict, control: bool) -> jax.Array:
    g = {k: v.astype(F32) for k, v in g.items()}
    h = norm(x, g, "final_norm", m)
    if control:
        h = int8_rows(h, -1)
    if m["tie_embeddings"]:
        e = g["embed.embedding"]
        return mm(h, (int8_rows(e, 1) if control else e).T)
    w = g["head.w"]
    return mm(h, int8_rows(w, 0) if control else w)


# ------------------------------------------------------------- operations

def matmul_params(m: dict) -> tuple[int, int]:
    """(parameters of the matmuls in all layers, of the output head)."""
    d, hd = m["d_model"], head_dim(m)
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    mlp = d * m["d_ff"] * (3 if m["glu"] else 2)
    return m["n_layers"] * (attn + mlp), d * m["vocab_size"]


def attention_flops(m: dict, ctx: int) -> float:
    """Scores and value mixing of one query against ``ctx`` keys."""
    return 4.0 * m["n_layers"] * ctx * m["n_heads"] * head_dim(m)


def decode_token_flops(m: dict, ctx: int) -> float:
    """One generated token whose query sees ``ctx`` keys (itself included)."""
    body, head = matmul_params(m)
    return 2.0 * (body + head) + attention_flops(m, ctx)


def prefill_flops(m: dict, prompt_len: int) -> float:
    """A whole prompt: every position through the layers, causal attention,
    and the head once (only the last position's logits are needed)."""
    body, head = matmul_params(m)
    causal_pairs = prompt_len * (prompt_len + 1) / 2.0
    attn = 4.0 * m["n_layers"] * causal_pairs * m["n_heads"] * head_dim(m)
    return 2.0 * body * prompt_len + attn + 2.0 * head
