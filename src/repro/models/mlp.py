"""MLP blocks (SwiGLU / GeGLU / ReLU), with the DSLOT digit-serial execution
mode for inference (the paper's technique as a first-class execution option).

When ``cfg.dslot.enabled`` and the activation is ReLU (the only case where the
early-negative-termination contract holds), the up-projection
matmul runs through the unified ``repro.layers.DslotDense`` API with fused
ReLU and per-tile early termination.  ``prepare_mlp_dslot`` attaches the
one-time weight-stationary lowering (``kernels.ops.dslot_prepare``) to every
up-projection in a params tree — scan-stacked groups included — so serving
executes against cached termination tables and block geometry (digit planes
themselves are derived in-kernel per call, never cached or materialized);
unprepared params fall back to trace-time lowering.  The runtime precision comes from the active
``repro.runtime`` precision scope (per-request budgets in serving), and
termination statistics are surfaced through ``repro.models.stats``.  The
up-projection, on either path, runs under the ``mlp_up`` named scope.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .layers import Params, apply_dense, init_dense
from .pspec import constrain

_ACTS = {
    "silu": jax.nn.silu,
    "gelu": jax.nn.gelu,
    "relu": lambda x: jnp.maximum(x, 0.0),
    "relu2": lambda x: jnp.square(jnp.maximum(x, 0.0)),   # squared ReLU
}


def init_mlp(cfg, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 3)
    p = {"up": init_dense(ks[0], cfg.d_model, cfg.d_ff, dt),
         "down": init_dense(ks[1], cfg.d_ff, cfg.d_model, dt)}
    if cfg.glu:
        p["gate"] = init_dense(ks[2], cfg.d_model, cfg.d_ff, dt)
    return p


def apply_mlp(p: Params, x: jax.Array, cfg) -> jax.Array:
    act = _ACTS[cfg.act]
    if cfg.dslot.enabled and cfg.act == "relu" and not cfg.glu:
        return _apply_mlp_dslot(p, x, cfg)
    with jax.named_scope("mlp_up"):
        up = constrain(apply_dense(p["up"], x), "b", None, "tp")
        gate = constrain(apply_dense(p["gate"], x), "b", None, "tp") \
            if cfg.glu else None
    h = act(gate) * up if cfg.glu else act(up)
    return apply_dense(p["down"], h)


def _dslot_up_layer(cfg):
    from repro.layers import DslotDense

    d = cfg.dslot
    return DslotDense(
        d_in=cfg.d_model, d_out=cfg.d_ff, name="mlp_up_dslot",
        n_bits=d.n_bits, n_planes=d.n_planes, relu=True, signed=True,
        sort_columns=d.sort_columns, block_m=d.block_m, block_n=d.block_n,
        block_k=d.block_k, use_pallas=d.use_pallas)


def _apply_mlp_dslot(p: Params, x: jax.Array, cfg) -> jax.Array:
    """Digit-serial inference path: fused up-proj + ReLU with early
    termination of provably-negative output tiles (paper Algorithm 1,
    tile-granular TPU adaptation), routed through the unified
    ``repro.layers.DslotDense`` layer API.  Uses the prepared state in
    ``p["up"]["dslot"]`` when ``prepare_mlp_dslot`` has run; the runtime
    precision scope (per-request plane budgets) overrides ``cfg.dslot``."""
    from . import stats

    layer = _dslot_up_layer(cfg)
    with jax.named_scope("mlp_up"):
        h, st = layer.apply(p["up"], x.astype(jnp.float32))
    stats.record("mlp_dslot_skipped_frac", st.skipped_frac)
    stats.record("mlp_dslot_planes_used",
                 jnp.mean(st.planes_used.astype(jnp.float32)))
    return apply_dense(p["down"], h.astype(x.dtype))


def mlp_uses_dslot(cfg) -> bool:
    """The digit-serial path applies: ReLU (termination contract), no GLU."""
    return bool(cfg.dslot.enabled and cfg.act == "relu" and not cfg.glu)


def prepare_mlp_dslot(params, cfg, mesh=None, tp_axis="model"):
    """Attach the one-time DSLOT lowering to every MLP up-projection in a
    model params tree.

    Walks the (nested dict/list/tuple) tree for MLP-shaped subtrees — a dict
    with ``up``/``down`` dense-param dicts — and stores a prepared
    ``DslotWeights`` under ``[...]["up"]["dslot"]``.  Scan-stacked weights
    (leading group axis, ndim 3) are prepared per-layer via ``vmap``, so the
    prepared tables slice correctly inside ``lax.scan`` over layers.
    Returns the params unchanged when the dslot path does not apply.

    ``mesh``/``tp_axis`` bake tensor parallelism into the prepared state:
    every digit-serial up-projection then executes N-sharded over the mesh
    (``kernels/ops.py`` module docs) — bit-identical outputs, one
    ``shard_map`` per layer inside whatever jit the caller wraps.
    """
    if not mlp_uses_dslot(cfg):
        return params
    from repro.kernels.ops import dslot_prepare

    d = cfg.dslot
    x_scale = None if d.act_scale is None else jnp.float32(d.act_scale)

    def prep_one(w):
        return dslot_prepare(
            w.astype(jnp.float32), n_bits=d.n_bits, relu=True, signed=True,
            sort_columns=d.sort_columns, block_m=d.block_m, block_n=d.block_n,
            block_k=d.block_k,
            backend="pallas" if d.use_pallas else "jnp", x_scale=x_scale,
            mesh=mesh, tp_axis=tp_axis)

    def walk(node):
        if isinstance(node, dict):
            if ("up" in node and "down" in node
                    and isinstance(node["up"], dict) and "w" in node["up"]
                    and "gate" not in node):
                w = node["up"]["w"]
                prepared = (jax.vmap(prep_one)(w) if w.ndim == 3
                            else prep_one(w))
                return {**node, "up": {**node["up"], "dslot": prepared}}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(v) for v in node]
        if isinstance(node, tuple):
            return tuple(walk(v) for v in node)
        return node

    return walk(params)
