"""Shared model layers: norms, rotary embeddings, token embedding, heads.

Parameters are plain nested dicts of jnp arrays (pytree-native — pjit shards
them via path-pattern rules in ``repro.train.sharding``).  Initializers take
explicit PRNG keys; every layer has a pure ``apply`` function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Params = dict


def _dtype(cfg):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------- norms

def init_norm(cfg, key=None) -> Params:
    if cfg.norm == "nonparam_ln":
        return {}                       # OLMo: no scale / bias
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((cfg.d_model,), jnp.float32),
                "bias": jnp.zeros((cfg.d_model,), jnp.float32)}
    return {"scale": jnp.ones((cfg.d_model,), jnp.float32)}


def apply_norm(p: Params, x: jax.Array, cfg) -> jax.Array:
    """Statistics in f32, elementwise normalize in the residual dtype — the
    f32 copy of the whole (B, S, D) stream is never materialized (matters:
    saved-carry stacks in the layer scan stay bf16, DESIGN.md §5)."""
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                          + cfg.norm_eps)
        return x * (r.astype(x.dtype)) * p["scale"].astype(x.dtype)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + cfg.norm_eps)
    out = (x - mu.astype(x.dtype)) * r.astype(x.dtype)
    if cfg.norm == "layernorm":
        out = out * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)
    return out


# ---------------------------------------------------------------- rotary

def rope_frequencies(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) with positions (..., S) int32."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)                       # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                            # (..., S, 1, D/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin,
                           xf2 * cos + xf1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- embeddings

def init_embedding(cfg, key) -> Params:
    scale = cfg.d_model ** -0.5
    emb = jax.random.normal(key, (cfg.vocab_size, cfg.d_model),
                            jnp.float32) * scale
    return {"embedding": emb.astype(_dtype(cfg))}


def embed_tokens(p: Params, tokens: jax.Array, cfg) -> jax.Array:
    return jnp.take(p["embedding"], tokens, axis=0)


def init_lm_head(cfg, key) -> Params:
    if cfg.tie_embeddings:
        return {}
    w = jax.random.normal(key, (cfg.d_model, cfg.vocab_size),
                          jnp.float32) * cfg.d_model ** -0.5
    return {"w": w.astype(_dtype(cfg))}


def lm_logits(head: Params, embed: Params, x: jax.Array, cfg) -> jax.Array:
    """The unembedding, under the ``logits`` named scope."""
    with jax.named_scope("logits"):
        if cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", x, embed["embedding"],
                              preferred_element_type=x.dtype)
        return jnp.einsum("...d,dv->...v", x, head["w"],
                          preferred_element_type=x.dtype)


# ---------------------------------------------------------------- dense

def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * d_in ** -0.5
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def apply_dense(p: Params, x: jax.Array) -> jax.Array:
    # preferred_element_type pins the dot OUTPUT to the weight dtype: the MXU
    # still accumulates in f32 internally, but row-parallel partial sums then
    # cross the all-reduce in bf16 (half the TP collective bytes and no f32
    # copies of the residual stream — measured 2 GiB/layer on deepseek-67b).
    y = jnp.einsum("...d,df->...f", x, p["w"],
                   preferred_element_type=p["w"].dtype)
    if "b" in p:
        y = y + p["b"]
    return y
