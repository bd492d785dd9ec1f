"""Mixture-of-Experts layer: top-k router + capacity-based dispatch.

Dispatch is scatter/gather based (O(T*D) data movement, no one-hot einsum
blow-up): each (token, choice) is assigned a slot ``expert * C + rank`` where
``rank`` is the token's arrival order within the expert (cumsum over the
token axis) and ``C`` the per-expert capacity.  In training, overflowing
tokens are dropped for that expert (standard GShard/Switch semantics,
capacity_factor controls the drop rate); their combine weight is zero so the
residual path carries them.  Serving is dropless (C = tokens) and pads of
ragged rows take no capacity.

Routing is softmax top-k, or sigmoid scores chosen with a correction bias
(``moe_router``); a shared expert may add to every token.  A layer may hold
a share of the experts (an expert-parallel chip's, ``experts_held`` from
``expert_offset``) while routing over all of them: it computes its own
experts' part of the result.

Expert FFN compute is a single batched einsum over (E, C, D) — per-expert
FLOPs proportional to *active* tokens only, which keeps the roofline's
MODEL_FLOPS/HLO_FLOPs ratio honest (DESIGN.md §5).

Expert-parallel (all_to_all) execution is provided separately in
``repro.distributed.expert_parallel`` via shard_map; this module's dense
einsum form is the pjit/GSPMD path (experts sharded over the model axis).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import Params, apply_dense, init_dense
from .mlp import _ACTS
from .pspec import constrain


def init_moe(cfg, key) -> Params:
    """Router over all ``n_experts``; expert weights for the
    ``experts_held_`` held here; a shared expert where ``moe_shared_ff``."""
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    E, El, D, F = cfg.n_experts, cfg.experts_held_, cfg.d_model, cfg.d_ff
    s = D ** -0.5
    p = {
        "router": (jax.random.normal(ks[0], (D, E), jnp.float32) * s
                   ).astype(jnp.float32),
        "up": (jax.random.normal(ks[1], (El, D, F), jnp.float32) * s
               ).astype(dt),
        "down": (jax.random.normal(ks[2], (El, F, D), jnp.float32)
                 * F ** -0.5).astype(dt),
    }
    if cfg.glu:
        p["gate"] = (jax.random.normal(ks[3], (El, D, F), jnp.float32) * s
                     ).astype(dt)
    if cfg.moe_router == "sigmoid":
        p["e_score_correction_bias"] = jnp.zeros((E,), jnp.float32)
    if cfg.moe_shared_ff:
        Fs = cfg.moe_shared_ff
        p["shared"] = {"up": init_dense(ks[4], D, Fs, dt),
                       "down": init_dense(ks[5], Fs, D, dt)}
    return p


def moe_capacity(cfg, n_tokens: int) -> int:
    per = n_tokens * cfg.top_k / cfg.n_experts
    cap = int(per * cfg.capacity_factor) + 1
    cap = max(cap, cfg.top_k)
    return -(-cap // 128) * 128   # 128-aligned so capacity slots shard evenly


def route(p: Params, flat: jax.Array, cfg
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k over all ``n_experts``: (weights, experts, router probs).

    ``softmax``: the top-k probabilities, renormalized.  ``sigmoid``: the
    experts are chosen by score plus ``e_score_correction_bias``, weighted
    by their scores alone, renormalized; either way times ``moe_scale``.

    The scores decide which experts run, so they are computed in full f32
    (a TPU's default f32 matmul rounds the router's weight to bf16)."""
    logits = jnp.einsum("...d,de->...e", flat.astype(jnp.float32),
                        p["router"], precision=jax.lax.Precision.HIGHEST)
    if cfg.moe_router == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(probs + p["e_score_correction_bias"],
                               cfg.top_k)
        gate = jnp.take_along_axis(probs, idx, axis=-1)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        gate, idx = jax.lax.top_k(probs, cfg.top_k)
    gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return gate * cfg.moe_scale, idx, probs


def apply_moe(p: Params, x: jax.Array, cfg, *,
              q_valid: jax.Array | None = None, dropless: bool = False
              ) -> tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss).  aux = load-balancing loss (Switch).

    The layer holds experts ``[expert_offset, expert_offset +
    experts_held_)`` and routes over all ``n_experts``: a token's choices
    of experts held elsewhere add nothing here (an expert-parallel chip's
    share, with no exchange), the shared expert adds to every token.

    Capacity: ``dropless`` (serving) or a decode step (S == 1) gives each
    held expert C = T rows, the most a token block can route to it since a
    token picks an expert at most once; otherwise (training) C comes from
    ``capacity_factor``.  ``q_valid`` (B, S) marks ragged rows' pads: they
    are routed nowhere and take no capacity.

    Records ``moe_rows_routed`` (the (token, held expert) rows routed) and
    ``moe_rows_computed`` (the rows the expert matmuls compute) through
    ``models/stats.py``."""
    from . import stats
    from .pspec import fsdp_size
    B, S, D = x.shape
    T = B * S
    valid = jnp.ones((B, S), bool) if q_valid is None else q_valid

    # Dispatch is computed per DATA SHARD (local capacity, as production MoE
    # systems do): a token-indexed scatter into a global buffer cannot be
    # partitioned by GSPMD (it replicates a multi-GB buffer and all-reduces
    # it — measured 60 GiB/device on mixtral prefill).  With a leading shard
    # axis everything — cumsum ranks, scatter, expert einsum, gather — stays
    # batched over that axis and shards cleanly.  Without a mesh G == 1 and
    # semantics are identical to global dispatch.
    G = fsdp_size() if B % max(fsdp_size(), 1) == 0 else 1
    Tl = T // G
    flat = x.reshape(G, Tl, D)
    valid = valid.reshape(G, Tl)

    # Long sequences stream through the experts in token BLOCKS (flash-style):
    # dispatch buffers scale with the block, not the sequence — a 1M-token
    # prefill would otherwise need ~4 GiB/device of (E*C, D) buffers.
    tb = min(Tl, 4096)
    nb = Tl // tb
    if nb > 1 and Tl % tb == 0 and S > 1:
        blocks = jnp.moveaxis(flat.reshape(G, nb, tb, D), 1, 0)
        vblocks = jnp.moveaxis(valid.reshape(G, nb, tb), 1, 0)
        C = tb if dropless else moe_capacity(cfg, tb)

        def one(args):
            return _moe_block(p, *args, cfg, C)

        ys, auxs, routed = jax.lax.map(one, (blocks, vblocks))
        y = jnp.moveaxis(ys, 0, 1).reshape(B, S, D)
        aux, routed, C = jnp.mean(auxs), jnp.sum(routed), C * nb
    else:
        C = Tl if dropless or S == 1 else moe_capacity(cfg, Tl)
        y, aux, routed = _moe_block(p, flat, valid, cfg, C)
        y = y.reshape(B, S, D)
    stats.record("moe_rows_routed", routed)
    stats.record("moe_rows_computed",
                 jnp.float32(G * cfg.experts_held_ * C))
    if "shared" in p:
        with jax.named_scope("shared"):
            sp = p["shared"]
            y = y + apply_dense(sp["down"],
                                _ACTS[cfg.act](apply_dense(sp["up"], x)))
    return y, aux


def _moe_block(p: Params, flat: jax.Array, valid: jax.Array, cfg, C: int
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One token block through the held experts.  flat: (G, Tl, D) — G
    data shards, capacity C per held expert and shard; valid: (G, Tl).
    Returns (y, aux, rows routed to held experts)."""
    G, Tl, D = flat.shape
    E, K = cfg.n_experts, cfg.top_k
    El, off = cfg.experts_held_, cfg.expert_offset
    act = _ACTS[cfg.act]
    with jax.named_scope("router"):
        gate_vals, expert_idx, probs = route(p, flat, cfg)   # (G, Tl, K)

        # ----- load-balancing auxiliary loss (Switch eq. 4), global means
        me = jnp.mean(probs, axis=(0, 1))                     # (E,)
        ce = jnp.mean(jnp.sum(jax.nn.one_hot(expert_idx, E), axis=2),
                      axis=(0, 1))
        aux = E * jnp.sum(me * ce) / K

        # ----- per-shard capacity ranks among the held experts' rows
        # (arrival order within expert); choices of experts held elsewhere
        # and pad tokens take no rank
        local = expert_idx - off
        held = (local >= 0) & (local < El) & valid[..., None]
        onehot = jax.nn.one_hot(jnp.where(held, local, El), El,
                                dtype=jnp.float32)            # (G, Tl, K, El)
        flat_choice = onehot.reshape(G, Tl * K, El)
        ranks = jnp.cumsum(flat_choice, axis=1) - flat_choice
        rank = jnp.sum(ranks * flat_choice, axis=-1).reshape(G, Tl, K)
        keep = held & (rank < C)
        slot = jnp.clip(local, 0, El - 1) * C \
            + jnp.minimum(rank, C - 1).astype(jnp.int32)

    with jax.named_scope("experts"):
        # ----- dispatch: per-shard scatter into (G, El*C, D)
        contrib = jnp.where(keep[..., None], 1.0, 0.0).astype(flat.dtype)
        src = (flat[:, :, None, :] * contrib).reshape(G, Tl * K, D)
        buf = jnp.zeros((G, El * C, D), flat.dtype)
        buf = jax.vmap(lambda b, s, u: b.at[s].add(u))(
            buf, slot.reshape(G, Tl * K), src)
        xb = constrain(buf.reshape(G, El, C, D), "b", None, None, None)

        # ----- expert FFN (batched over shards and experts; d_ff over
        # "model")
        up = constrain(jnp.einsum("gecd,edf->gecf", xb, p["up"], preferred_element_type=xb.dtype),
                       "b", None, None, "tp")
        if cfg.glu:
            h = act(constrain(jnp.einsum("gecd,edf->gecf", xb, p["gate"], preferred_element_type=xb.dtype),
                              "b", None, None, "tp")) * up
        else:
            h = act(up)
        yb = constrain(jnp.einsum("gecf,efd->gecd", h, p["down"], preferred_element_type=h.dtype),
                       "b", None, None, None).reshape(G, El * C, D)

        # ----- combine: per-shard gather, weight by gate
        gathered = jax.vmap(lambda y_, s: y_[s])(
            yb, slot.reshape(G, Tl * K)).reshape(G, Tl, K, D)
        w = (gate_vals * keep).astype(gathered.dtype)
        y = jnp.einsum("gtkd,gtk->gtd", gathered, w)
    return y, aux.astype(jnp.float32), jnp.sum(keep.astype(jnp.float32))
