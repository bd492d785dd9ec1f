"""Block assembly: pattern-driven layer stacks with scan-over-layers + remat.

A model is a sequence of blocks drawn from the config's ``block_pattern``
(tiled to ``n_layers``): "attn" (self-attention + MLP), "attn_cross" (adds
cross-attention, enc-dec decoder), "moe" (attention + MoE-FFN), "ssm"
(mamba2 mixer), "rglru" (RG-LRU mixing + MLP).

Layers are stacked per pattern position and iterated with ``jax.lax.scan``
(+ ``jax.checkpoint`` rematerialization), so the lowered HLO is O(pattern)
regardless of depth — a 95-layer model compiles as one scanned block.  The
pattern remainder (e.g. recurrentgemma's 26 = 3*8 + 2) runs unscanned.

Caches are pytrees mirroring the parameter stacking.  A decode step carries
the stacked KV rings through the scan and writes each layer's new entries
in place; recurrent states ride the scan's inputs and outputs.  ``mode="decode"`` accepts multi-token inputs too:
attention writes each chunk's KV at its positions into the per-sequence
rings — batched, at ragged per-sequence offsets, with ``q_valid`` masking
the ring writes of right-padded rows — and recurrent mixers advance their
carried state through masked scans where pad positions are exact identity
steps (``apply_ssm`` / ``apply_rglru``).  Every layer kind accepts ragged
``q_valid`` batches.  This is the ``Model.extend`` path that batched
chunked prefill (``docs/serving.md``) is built on.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from . import stats as model_stats
from .attention import attention_forward, init_attention, init_kv_cache
from .layers import Params, apply_norm, init_norm
from .mlp import apply_mlp, init_mlp
from .moe import apply_moe, init_moe
from .rglru import RGLRUState, apply_rglru, init_rglru
from .ssm import SSMState, apply_ssm, init_ssm


# ------------------------------------------------------------- single layer

# Named scopes: the ops of each sub-layer carry ``attn/`` or ``mlp/`` in
# their HLO ``op_name`` (inside the layer scan's body too), so a profile
# attributes device time by layer; inputs (the norms) stay outside.

def _attn(p: Params, x: jax.Array, cfg, **kw):
    with jax.named_scope("attn"):
        return attention_forward(p, x, cfg, **kw)


def _mlp(p: Params, x: jax.Array, cfg) -> jax.Array:
    with jax.named_scope("mlp"):
        return apply_mlp(p, x, cfg)


def init_layer(cfg, key, kind: str) -> Params:
    ks = jax.random.split(key, 4)
    if kind == "ssm":
        return {"norm": init_norm(cfg), "mixer": init_ssm(cfg, ks[0])}
    if kind == "rglru":
        return {"norm1": init_norm(cfg), "mixer": init_rglru(cfg, ks[0]),
                "norm2": init_norm(cfg), "mlp": init_mlp(cfg, ks[1])}
    if kind == "moe":
        return {"norm1": init_norm(cfg), "attn": init_attention(cfg, ks[0]),
                "norm2": init_norm(cfg), "moe": init_moe(cfg, ks[1])}
    if kind == "attn_cross":
        return {"norm1": init_norm(cfg), "attn": init_attention(cfg, ks[0]),
                "normx": init_norm(cfg),
                "cross": init_attention(cfg, ks[1], cross=True),
                "norm2": init_norm(cfg), "mlp": init_mlp(cfg, ks[2])}
    # "attn"
    return {"norm1": init_norm(cfg), "attn": init_attention(cfg, ks[0]),
            "norm2": init_norm(cfg), "mlp": init_mlp(cfg, ks[1])}


def init_layer_cache(cfg, kind: str, batch: int, seq_len: int,
                     enc_len: int, dtype) -> Any:
    d_inner = cfg.ssm_expand * cfg.d_model
    if kind == "ssm":
        from .ssm import _dims
        _, H, P, N, G = _dims(cfg)
        conv_ch = d_inner + 2 * G * N
        return SSMState(
            conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_ch), dtype),
            ssm=jnp.zeros((batch, H, P, N), jnp.float32))
    if kind == "rglru":
        w = cfg.rnn_width or cfg.d_model
        return RGLRUState(conv=jnp.zeros((batch, 3, w), dtype),
                          h=jnp.zeros((batch, w), jnp.float32))
    self_cache = init_kv_cache(cfg, batch, seq_len, dtype)
    if kind == "attn_cross":
        return (self_cache,
                init_kv_cache(cfg, batch, enc_len, dtype, cross=True))
    return self_cache


def apply_layer(p: Params, x: jax.Array, cfg, kind: str, *,
                positions: jax.Array, cache: Any = None,
                enc_out: jax.Array | None = None, mode: str = "train",
                causal: bool = True, cache_len: int | None = None,
                q_valid: jax.Array | None = None,
                layer: jax.Array | None = None
                ) -> tuple[jax.Array, Any, jax.Array]:
    """Returns (x, new_cache, aux_loss).

    ``q_valid``: (B, S) bool for ragged batched forwards — pad rows skip
    the KV-ring write in attention kinds (see ``attention_forward``) and
    are exact identity steps in the recurrent mixers (``apply_ssm`` /
    ``apply_rglru``), so carried state only ever advances past real tokens.
    ``layer``: the self-attention ring's index in a stacked (scanned)
    ring cache; the cache then is, and the returned one is, the stack.
    """
    aux = jnp.zeros((), jnp.float32)
    return_cache = mode == "prefill"
    use_cache = mode == "decode"

    if kind == "ssm":
        h, new_state = apply_ssm(p["mixer"], apply_norm(p["norm"], x, cfg),
                                 cfg, state=cache if use_cache else None,
                                 return_state=return_cache or use_cache,
                                 q_valid=q_valid)
        return x + h, new_state, aux

    if kind == "rglru":
        h, new_state = apply_rglru(p["mixer"], apply_norm(p["norm1"], x, cfg),
                                   cfg, state=cache if use_cache else None,
                                   return_state=return_cache or use_cache,
                                   q_valid=q_valid)
        x = x + h
        x = x + _mlp(p["mlp"], apply_norm(p["norm2"], x, cfg), cfg)
        return x, new_state, aux

    if kind == "attn_cross":
        self_cache, cross_cache = cache if cache is not None else (None, None)
        h, new_self = _attn(
            p["attn"], apply_norm(p["norm1"], x, cfg), cfg,
            positions=positions, cache=self_cache if use_cache else None,
            causal=causal, return_cache=return_cache, cache_len=cache_len,
            q_valid=q_valid, layer=layer)
        x = x + h
        if use_cache:
            # decode: static cross cache built at prefill
            h, cross_cache = _attn(
                p["cross"], apply_norm(p["normx"], x, cfg), cfg,
                positions=positions, cache=cross_cache, is_cross=True,
                causal=False)
        else:
            h, cross_cache = _attn(
                p["cross"], apply_norm(p["normx"], x, cfg), cfg,
                positions=positions, kv_x=enc_out, causal=False,
                return_cache=return_cache)
        x = x + h
        x = x + _mlp(p["mlp"], apply_norm(p["norm2"], x, cfg), cfg)
        return x, (new_self, cross_cache), aux

    # attn / moe
    h, new_cache = _attn(
        p["attn"], apply_norm(p["norm1"], x, cfg), cfg, positions=positions,
        cache=cache if use_cache else None, causal=causal,
        return_cache=return_cache, cache_len=cache_len, q_valid=q_valid,
        layer=layer)
    x = x + h
    if kind == "moe":
        h, aux = apply_moe(p["moe"], apply_norm(p["norm2"], x, cfg), cfg)
    else:
        h = _mlp(p["mlp"], apply_norm(p["norm2"], x, cfg), cfg)
    return x + h, new_cache, aux


# ------------------------------------------------------------- layer stacks

class Stack:
    """Pattern-tiled stack of layers with scan-over-groups execution."""

    def __init__(self, cfg, pattern: tuple[str, ...], n_layers: int,
                 causal: bool = True):
        self.cfg = cfg
        self.n_layers = n_layers
        self.causal = causal
        # one scan step covers `scan_unroll` pattern periods (fewer saved
        # carries under full remat; recompute cost is unchanged)
        unroll = max(1, cfg.scan_unroll)
        self.pattern = tuple(pattern) * unroll
        self.period = len(self.pattern)
        if cfg.scan_layers and n_layers >= 2 * self.period:
            self.n_groups = n_layers // self.period
            self.n_rest = n_layers % self.period
        else:
            self.n_groups = 0
            self.n_rest = n_layers

    @property
    def rest_kinds(self) -> tuple[str, ...]:
        full = (self.pattern * (-(-self.n_layers // self.period)))
        return full[self.n_groups * self.period: self.n_layers]

    def init(self, key) -> Params:
        p: Params = {"groups": [], "rest": []}
        keys = jax.random.split(key, self.n_layers)
        ki = 0
        for pos in range(self.period if self.n_groups else 0):
            kind = self.pattern[pos]
            layers = []
            for g in range(self.n_groups):
                layers.append(init_layer(self.cfg, keys[ki], kind))
                ki += 1
            p["groups"].append(jax.tree.map(
                lambda *xs: jnp.stack(xs), *layers))
        for kind in self.rest_kinds:
            p["rest"].append(init_layer(self.cfg, keys[ki], kind))
            ki += 1
        return p

    def init_cache(self, batch: int, seq_len: int, enc_len: int, dtype):
        c = {"groups": [], "rest": []}
        for pos in range(self.period if self.n_groups else 0):
            kind = self.pattern[pos]
            per = [init_layer_cache(self.cfg, kind, batch, seq_len, enc_len,
                                    dtype) for _ in range(self.n_groups)]
            c["groups"].append(jax.tree.map(lambda *xs: jnp.stack(xs), *per))
        for kind in self.rest_kinds:
            c["rest"].append(init_layer_cache(self.cfg, kind, batch, seq_len,
                                              enc_len, dtype))
        return c

    def apply(self, p: Params, x: jax.Array, *, positions, caches=None,
              enc_out=None, mode: str = "train", cache_len: int | None = None,
              q_valid: jax.Array | None = None):
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)
        new_caches = {"groups": [], "rest": []}

        if self.n_groups:
            # Decode: each attention kind's stacked ring rides the scan's
            # CARRY, and every layer writes only its new entries into the
            # stack in place, at its group index (``layer``).  Passed as
            # xs/ys instead, each step would slice every layer's whole ring
            # out of the stack and write it back.  Recurrent states (small)
            # and the static cross caches go through xs; only the recurrent
            # states come back as ys.
            carried = caches is not None
            rings, per_layer = None, None
            if carried:
                rings, per_layer = zip(*(
                    _split_cache(kind, c)
                    for kind, c in zip(self.pattern, caches["groups"])))

            def group_body(carry, layer_inputs):
                x, rings = carry
                params_g, g, per_g = layer_inputs
                aux_g = jnp.zeros((), jnp.float32)
                rings = list(rings) if carried else None
                outs = []
                # Layer statistics recorded inside a scanned body would be
                # scan-local tracers; capture them here and thread them out
                # as scan outputs, re-recording the stacked values after the
                # scan — makes the stats side channel scan-safe.
                with model_stats.collect() as sink:
                    for pos, kind in enumerate(self.pattern):
                        c = _join_cache(kind, rings[pos], per_g[pos]) \
                            if carried else None
                        x, nc, aux = apply_layer(
                            params_g[pos], x, cfg, kind, positions=positions,
                            cache=c, enc_out=enc_out, mode=mode,
                            causal=self.causal, cache_len=cache_len,
                            q_valid=q_valid, layer=g if carried else None)
                        if carried:
                            rings[pos], nc = _split_cache(kind, nc)
                            if kind == "attn_cross":
                                nc = None        # read only: not carried out
                        outs.append(nc)
                        aux_g = aux_g + aux
                recs = {k: tuple(v) for k, v in sink.items()}
                rings = tuple(rings) if carried else None
                return (x, rings), (tuple(outs), aux_g, recs)

            body = group_body
            if cfg.remat and mode == "train":
                body = jax.checkpoint(
                    group_body,
                    policy=jax.checkpoint_policies.nothing_saveable)

            (x, rings), (ncs, auxs, recs) = jax.lax.scan(
                body, (x, rings),
                (tuple(p["groups"]), jnp.arange(self.n_groups), per_layer))
            if carried:
                new_caches["groups"] = [
                    _join_cache(kind, ring,
                                per if kind == "attn_cross" else nc)
                    for kind, ring, per, nc in zip(self.pattern, rings,
                                                   per_layer, ncs)]
            elif mode == "prefill":
                new_caches["groups"] = list(ncs)
            aux_total = aux_total + jnp.sum(auxs)
            for k, vals in recs.items():
                for v in vals:       # leading axis = n_groups (scan steps)
                    model_stats.record(k, v)

        for i, kind in enumerate(self.rest_kinds):
            c = None if caches is None else caches["rest"][i]
            x, nc, aux = apply_layer(p["rest"][i], x, cfg, kind,
                                     positions=positions, cache=c,
                                     enc_out=enc_out, mode=mode,
                                     causal=self.causal, cache_len=cache_len,
                                     q_valid=q_valid)
            new_caches["rest"].append(nc)
            aux_total = aux_total + aux

        return x, new_caches, aux_total


def _split_cache(kind: str, cache):
    """A layer cache as (self-attention ring, the rest): the rest is the
    static cross cache of ``attn_cross``, a recurrent kind's state, or
    None."""
    if kind in ("attn", "moe"):
        return cache, None
    if kind == "attn_cross":
        return cache
    return None, cache


def _join_cache(kind: str, ring, rest):
    """Inverse of ``_split_cache``."""
    if kind in ("attn", "moe"):
        return ring
    if kind == "attn_cross":
        return (ring, rest)
    return rest
