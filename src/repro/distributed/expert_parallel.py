"""Expert-parallel MoE dispatch via all_to_all (shard_map).

The pjit path in ``repro.models.moe`` lets GSPMD shard the expert einsum
(experts' d_ff over the model axis).  True expert parallelism instead places
``E / ep`` experts per device and routes tokens with two all_to_alls:

    tokens -> [a2a] -> expert-local FFN -> [a2a back] -> combine

which turns the expert weights' all-gather traffic into activation-sized
a2a traffic — the right trade when tokens-per-device << expert size (the
mixtral-8x22b regime).  Used as a §Perf alternative; numerical equivalence
with the dense-einsum path is tested on an 8-device CPU mesh.

This implementation keeps the capacity-slot layout of ``apply_moe``: after
the (T, K) -> (E, C, D) dispatch buffer is built locally, the E axis is
exchanged so each device holds its experts' slots for ALL source devices,
runs the FFN, and the inverse a2a returns outputs to token owners.

Per-expert plane budgets (``expert_planes``): the DSLOT digit-serial idea
applied at expert granularity — each expert's input activations are
truncated to that expert's most significant ``expert_planes[e]`` digit
planes (MSDF order) before its FFN runs, so cold/degradable experts spend
fewer digit planes than hot ones.  The budget vector shards over ``axis``
with the expert weights (each device truncates only its own experts,
after the first a2a).  Budgets >= ``n_bits`` are EXACT no-ops, preserving
the dense-forward equivalence; budgets below truncate deterministically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.models.mlp import _ACTS
from repro.models.moe import moe_capacity


def _truncate_planes(xb, planes, n_bits):
    """Keep each local expert's top ``planes[e]`` MSDF digit planes of its
    (C, D) input slice.  ``planes >= n_bits`` rows pass through untouched
    (bit-exact): the where() below selects the raw input, so quantization
    round-off never leaks into full-budget experts."""
    qmax = float(2 ** (n_bits - 1) - 1)
    amax = jnp.maximum(jnp.max(jnp.abs(xb), axis=(1, 2)), 1e-12)  # (E/ep,)
    step = (amax / qmax)[:, None, None]
    q = jnp.clip(jnp.round(xb / step), -qmax, qmax).astype(jnp.int32)
    shift = jnp.clip(n_bits - planes, 0, n_bits).astype(jnp.int32)
    kept = jnp.right_shift(jnp.abs(q), shift[:, None, None])
    kept = jnp.left_shift(kept, shift[:, None, None])
    xq = (jnp.sign(q) * kept).astype(xb.dtype) * step
    return jnp.where((planes < n_bits)[:, None, None], xq, xb)


def apply_moe_ep(p, x, cfg, mesh: Mesh, axis: str = "model",
                 expert_planes=None, n_bits: int = 8):
    """Expert-parallel MoE forward.  x: (B, S, D) sharded P((pod,data)...)
    on batch; experts sharded over ``axis``.  Requires E % mesh[axis] == 0.
    Returns (y, aux) like ``apply_moe``.

    ``expert_planes``: optional (E,) i32 per-expert digit-plane budget
    (module docstring) — entries >= ``n_bits`` are exact no-ops.
    """
    E, K = cfg.n_experts, cfg.top_k
    ep = mesh.shape[axis]
    assert E % ep == 0, (E, ep)
    act = _ACTS[cfg.act]
    planes_all = (jnp.full((E,), n_bits, jnp.int32) if expert_planes is None
                  else jnp.asarray(expert_planes, jnp.int32))
    assert planes_all.shape == (E,), planes_all.shape

    def body(xl, router, up, gate, down, planes):
        # xl: (Bl, S, D) tokens local to this device along batch;
        # up/gate/down: (E/ep, D, F) — this device's experts.
        Bl, S, D = xl.shape
        T = Bl * S
        C = moe_capacity(cfg, T)
        flat = xl.reshape(T, D)
        logits = jnp.einsum("td,de->te", flat.astype(jnp.float32), router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)
        ce = jnp.mean(jnp.sum(onehot, axis=1), axis=0)
        aux = E * jnp.sum(me * ce) / K

        flat_choice = onehot.reshape(T * K, E)
        ranks = jnp.cumsum(flat_choice, axis=0) - flat_choice
        rank = jnp.sum(ranks * flat_choice, axis=-1).reshape(T, K)
        keep = rank < C
        slot = expert_idx * C + jnp.minimum(rank, C - 1).astype(jnp.int32)

        buf = jnp.zeros((E * C, D), flat.dtype)
        contrib = jnp.where(keep[..., None], 1.0, 0.0).astype(flat.dtype)
        buf = buf.at[slot.reshape(-1)].add(
            (flat[:, None, :] * contrib).reshape(T * K, D))
        xb = buf.reshape(E, C, D)

        # ---- a2a: exchange the expert axis; gain a source-device axis.
        # (E, C, D) -> (ep, E/ep, C, D) -> a2a over ep -> each device holds
        # its E/ep experts x (ep sources) x C slots.
        xb = xb.reshape(ep, E // ep, C, D)
        xb = jax.lax.all_to_all(xb, axis, split_axis=0, concat_axis=0,
                                tiled=False)                 # (ep, E/ep, C, D)
        xb = jnp.moveaxis(xb, 0, 1).reshape(E // ep, ep * C, D)

        # per-expert digit-plane budget: truncate this device's experts'
        # inputs to their granted MSDF planes (exact no-op at full budget)
        xb = _truncate_planes(xb, planes, n_bits)

        h = jnp.einsum("ecd,edf->ecf", xb, up)
        if cfg.glu:
            h = act(jnp.einsum("ecd,edf->ecf", xb, gate)) * h
        else:
            h = act(h)
        yb = jnp.einsum("ecf,efd->ecd", h, down)             # (E/ep, ep*C, D)

        # ---- inverse a2a back to token owners
        yb = jnp.moveaxis(yb.reshape(E // ep, ep, C, D), 1, 0)
        yb = jax.lax.all_to_all(yb, axis, split_axis=0, concat_axis=0,
                                tiled=False)
        yb = yb.reshape(E * C, D)

        gathered = yb[slot.reshape(-1)].reshape(T, K, D)
        w = (gate_vals * keep).astype(gathered.dtype)
        y = jnp.einsum("tkd,tk->td", gathered, w).reshape(Bl, S, D)
        return y, aux.astype(jnp.float32)[None]

    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    bspec = fsdp if fsdp else None
    # outputs are replicated across the model axis by construction (every
    # model rank holds the same tokens); the static vma checker cannot prove
    # data-dependent replication, so it is disabled.
    in_specs = (P(bspec), P(), P(axis), P(axis), P(axis), P(axis))
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=(P(bspec), P(axis)), check_vma=False)
    y, aux = sm(x, p["router"], p["up"], p.get("gate", p["up"]), p["down"],
                planes_all)
    return y, jnp.mean(aux)
