"""Attention: GQA/MQA, full-causal, sliding-window/local, cross; flash-style.

Memory discipline: scores are never materialized for the full sequence.
``flash_attention`` scans KV in chunks with running-max online softmax
(O(S * chunk) score memory); the sliding-window path additionally chunks the
query axis and slices only the in-window KV span (O(S * W) compute — this is
what makes the `long_500k`/SWA cells sub-quadratic).

Decode uses a ring-buffer KV cache: slot = position % capacity, with an
explicit per-slot position array for exact masking.  Full attention uses
capacity = seq_len (no wraparound); SWA uses capacity = window, so the cache
footprint of a 500k-token stream is O(window).

A ring stores K and V as (B, C, Hkv * D), every head of a position in one
minor axis: a decode step writes a new token as one contiguous row, and
``ring_attention`` reads each layer's ring once, as it lies, whatever the
head width (a (..., Hkv, D) array with D = 64 is laid out position-minor on
a TPU, and XLA then relays whole rings out and back each step).  Rings may
carry a leading layer axis (a scanned stack's caches): ``layer`` picks the
layer, and only its new entries are written, in place in the stack.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .layers import Params, apply_dense, apply_rope, init_dense
from .pspec import constrain, head_scheme

_NEG_INF = -1e30


class KVCache(NamedTuple):
    k: jax.Array          # (B, C, Hkv * D): the heads share the minor axis
    v: jax.Array          # (B, C, Hkv * D)
    positions: jax.Array  # (B, C) int32 per-sequence ring positions, -1 =
                          # empty.  Per-sequence (not shared) so a slot pool
                          # can hold requests at different decode depths.


def to_ring(x: jax.Array) -> jax.Array:
    """Token-major (B, S, Hkv, D) -> the ring's stored (B, S, Hkv * D)."""
    return x.reshape(*x.shape[:-2], -1)


def from_ring(x: jax.Array, head_dim: int) -> jax.Array:
    """The ring's stored (B, C, Hkv * D) -> token-major (B, C, Hkv, D)."""
    return x.reshape(*x.shape[:-1], -1, head_dim)


def _layer_of(ring: jax.Array, layer) -> jax.Array:
    return ring if layer is None else ring[layer]


def _rope(x: jax.Array, positions: jax.Array, cfg) -> jax.Array:
    """Rotary positions, unless the configuration has none (``rope``)."""
    return apply_rope(x, positions, cfg.rope_theta) if cfg.rope else x


def init_attention(cfg, key, cross: bool = False) -> Params:
    dt = jnp.dtype(cfg.dtype)
    hd = cfg.head_dim_
    ks = jax.random.split(key, 4)
    return {
        "wq": init_dense(ks[0], cfg.d_model, cfg.n_heads * hd, dt,
                         bias=cfg.qkv_bias),
        "wk": init_dense(ks[1], cfg.d_model, cfg.n_kv_heads * hd, dt,
                         bias=cfg.qkv_bias),
        "wv": init_dense(ks[2], cfg.d_model, cfg.n_kv_heads * hd, dt,
                         bias=cfg.qkv_bias),
        "wo": init_dense(ks[3], cfg.n_heads * hd, cfg.d_model, dt),
    }


def cache_capacity(cfg, seq_len: int) -> int:
    if cfg.attn_type == "swa" and cfg.window:
        return min(seq_len, cfg.window)
    return seq_len


def init_kv_cache(cfg, batch: int, seq_len: int, dtype, *,
                  cross: bool = False) -> KVCache:
    """A zero ring of capacity ``cache_capacity(cfg, seq_len)``, empty
    (-1 positions); ``cross``: a static encoder cache of ``seq_len``
    entries, every position valid."""
    C = seq_len if cross else cache_capacity(cfg, seq_len)
    shape = (batch, C, cfg.n_kv_heads * cfg.head_dim_)
    pos = jnp.arange(C, dtype=jnp.int32) if cross \
        else jnp.full((C,), -1, jnp.int32)
    # K and V in buffers of their own: a program that donates the cache
    # cannot take one buffer twice
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   positions=jnp.broadcast_to(pos[None], (batch, C)))


# ------------------------------------------------------------------ softmax core

def _attend_block(q, k, v, mask, m, l, acc):
    """One online-softmax update.  q:(B,Sq,Hkv,G,D) k/v:(B,Ck,Hkv,D)
    mask:(Sq,Ck) or (B,Sq,Ck); m,l:(B,Sq,Hkv,G) acc:(B,Sq,Hkv,G,D)."""
    s = jnp.einsum("bqhgd,bkhd->bqhgk", q, k,
                   preferred_element_type=jnp.float32)
    if mask.ndim == 2:
        mask = mask[None]
    s = jnp.where(mask[:, :, None, None, :], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    # bf16 probabilities for the PV matmul (standard flash practice): halves
    # the per-chunk residuals saved for the backward pass, f32 accumulation.
    acc_new = acc * corr[..., None] + jnp.einsum(
        "bqhgk,bkhd->bqhgd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32)
    return m_new, l_new, acc_new


def ring_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int
                   ) -> jax.Array:
    """One query per sequence against whole rings as stored, (B, C,
    Hkv * D): one un-chunked softmax block.  Both contractions are matrix
    products over the ring's minor axis as it lies in memory, each against
    block-diagonal queries (head h's query in head h's rows, zeros
    elsewhere), so no per-head relayout of the ring is needed; the zeros
    add exactly nothing, and Hq extra operations per ring byte keep the
    step bound by the ring's read.  Keeps the ring shardable along its
    capacity axis (context parallelism): the softmax reductions over it
    become tiny cross-device all-reduces instead of a scan over a sharded
    axis.

    q: (B, 1, Hq, D); positions as in ``flash_attention``.  Returns
    (B, 1, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    C = k.shape[1]
    Hkv = k.shape[2] // D
    G = Hq // Hkv
    f32 = jnp.float32
    qg = (q * D ** -0.5).reshape(B, Hkv, G, D)
    qp = q_pos if q_pos.ndim == 2 else q_pos[None]           # (b?, 1)
    kp = k_pos if k_pos.ndim == 2 else k_pos[None]           # (b?, C)
    mask = kp >= 0
    if causal:
        mask = mask & (kp <= qp)
    if window:
        mask = mask & (kp > qp - window)
    eye = jnp.eye(Hkv, dtype=q.dtype)
    qb = jnp.einsum("bhgd,hk->bhdkg", qg, eye).reshape(B, Hkv * D, Hq)
    s = jnp.einsum("bcx,bxn->bcn", k, qb,
                   preferred_element_type=f32).reshape(B, C, Hkv, G)
    s = jnp.where(mask[:, :, None, None], s, _NEG_INF)
    m = jnp.max(s, axis=1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=1)                                   # (B,Hkv,G)
    r = jnp.einsum("bcn,bcx->bnx", p.reshape(B, C, Hq).astype(v.dtype), v,
                   preferred_element_type=f32)
    # keep each head's own block: (B, Hkv, G, Hkv, D) -> (B, Hkv, G, D)
    acc = jnp.moveaxis(jnp.diagonal(r.reshape(B, Hkv, G, Hkv, D),
                                    axis1=1, axis2=3), -1, 1)
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def flash_attention(q, k, v, q_pos, k_pos, *, causal: bool, window: int,
                    chunk: int) -> jax.Array:
    """Chunked-KV online-softmax attention.

    q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D); positions int32 arrays
    (q_pos: (Sq,) or per-sequence (B, Sq); k_pos: (Sk,) or (B, Sk); k_pos
    may contain -1 = invalid slot).  2-D positions work on every path: the
    decode form (Sq == 1, a slot pool whose sequences sit at different
    depths: ``ring_attention``) and the generic chunked-KV scan (Sq > 1,
    batched multi-token cache extension at ragged per-sequence offsets —
    each sequence gets its own causal/window mask against its own ring
    positions).  Shared 1-D positions keep the cheaper (Sq, ck) per-chunk
    mask.  GQA folds Hq into (Hkv, G).  Returns (B, Sq, Hq, D) in q.dtype.
    """
    B, Sq, Hq, D = q.shape
    if Sq == 1:
        return ring_attention(q, to_ring(k), to_ring(v), q_pos, k_pos,
                              causal=causal, window=window)
    Hkv = k.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    qg = (q * scale).reshape(B, Sq, Hkv, G, D)

    Sk = k.shape[1]
    shared = q_pos.ndim == 1 and k_pos.ndim == 1
    ck = min(chunk, Sk)
    n_chunks = -(-Sk // ck)
    pad = n_chunks * ck - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0),) * (k_pos.ndim - 1) + ((0, pad),),
                        constant_values=-1)

    kc = k.reshape(B, n_chunks, ck, Hkv, D)
    vc = v.reshape(B, n_chunks, ck, Hkv, D)
    if shared:
        pc = k_pos.reshape(n_chunks, ck)
    else:
        # per-sequence positions: each batch row masks against its OWN ring
        # offsets, so the mask carries the batch axis ((B, Sq, ck) instead of
        # a shared (Sq, ck)) and the KV-position chunks are scanned per-row.
        qp = q_pos if q_pos.ndim == 2 \
            else jnp.broadcast_to(q_pos[None], (B, Sq))
        kp = k_pos if k_pos.ndim == 2 \
            else jnp.broadcast_to(k_pos[None], (B, k_pos.shape[-1]))
        pc = jnp.moveaxis(kp.reshape(B, n_chunks, ck), 1, 0)

    m0 = jnp.full((B, Sq, Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, Hkv, G, D), jnp.float32)

    def body(carry, inputs):
        m, l, acc = carry
        kb, vb, pb = inputs
        valid = pb >= 0
        if shared:
            mask = valid[None, :]
            if causal:
                mask = mask & (pb[None, :] <= q_pos[:, None])
            if window:
                mask = mask & (pb[None, :] > q_pos[:, None] - window)
        else:
            mask = valid[:, None, :]
            if causal:
                mask = mask & (pb[:, None, :] <= qp[:, :, None])
            if window:
                mask = mask & (pb[:, None, :] > qp[:, :, None] - window)
        m, l, acc = _attend_block(qg, kb, vb, mask, m, l, acc)
        return (m, l, acc), None

    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0), pc))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(B, Sq, Hq, D).astype(q.dtype)


def chunked_causal_attention(q, k, v, q_pos, k_pos, *, chunk: int
                             ) -> jax.Array:
    """Full causal attention with BOTH axes chunked: outer map over query
    chunks, inner flash scan over KV.  Bounds the score/mask working set to
    (B, cq, H, ck) regardless of sequence length — required for 32k+ prefill
    to fit HBM (the unchunked-query form hoists O(S^2/ck) masks)."""
    B, Sq, Hq, D = q.shape
    cq = min(chunk, Sq)
    n_q = -(-Sq // cq)
    pad_q = n_q * cq - Sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)

    def one_chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * cq, cq)
        return flash_attention(qs, k, v, qp, k_pos, causal=True, window=0,
                               chunk=chunk)

    outs = jax.lax.map(one_chunk, jnp.arange(n_q))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_q * cq, Hq, D)
    return out[:, :Sq]


def swa_attention(q, k, v, q_pos, k_pos, *, window: int, q_chunk: int
                  ) -> jax.Array:
    """Sub-quadratic sliding-window attention: chunk queries, slice only the
    in-window KV span per chunk.  Compute O(S * (W + cq)), not O(S^2)."""
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    cq = min(q_chunk, Sq)
    n_q = -(-Sq // cq)
    pad_q = n_q * cq - Sq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-1)
    span = min(Sk, window + cq)

    def one_chunk(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * cq, cq, axis=1)
        qp = jax.lax.dynamic_slice_in_dim(q_pos, i * cq, cq)
        # KV span covering (chunk_start - window, chunk_end]
        start = jnp.clip(i * cq + cq - span, 0, Sk - span)
        ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        kp = jax.lax.dynamic_slice_in_dim(k_pos, start, span)
        return flash_attention(qs, ks, vs, qp, kp, causal=True,
                               window=window, chunk=span)

    outs = jax.lax.map(one_chunk, jnp.arange(n_q))       # (n_q, B, cq, Hq, D)
    out = jnp.moveaxis(outs, 0, 1).reshape(B, n_q * cq, Hq, D)
    return out[:, :Sq]


# ------------------------------------------------------------------ module API

def attention_forward(p: Params, x: jax.Array, cfg, *, positions: jax.Array,
                      cache: KVCache | None = None,
                      kv_x: jax.Array | None = None,
                      causal: bool = True,
                      return_cache: bool = False,
                      is_cross: bool = False,
                      cache_len: int | None = None,
                      q_valid: jax.Array | None = None,
                      layer: jax.Array | None = None
                      ) -> tuple[jax.Array, KVCache | None]:
    """Full attention pass (train / prefill / decode / cross).

    x: (B, S, d_model).  positions: (S,) shared or (B, S) per-sequence int32
    absolute positions.
    cache: when given and S is small (decode), new KV are appended (ring) and
    attention runs against the cache; when ``return_cache`` on a long pass
    (prefill), the cache is built from this pass's KV.
    layer: with a cache whose leaves carry a leading layer axis (a scanned
    stack's rings), the layer this pass reads and writes; the returned
    cache is the whole stack with only this layer's new entries written.
    kv_x: encoder output for cross-attention (keys/values from there, no
    causal mask, no rope on cross keys beyond their own positions).
    q_valid: optional (B, S) bool — ragged batched cache extension.  Rows
    where it is False are right-padding of a shorter chunk: their KV is NOT
    written into the ring (the scatter writes back what the ring already
    holds at those slots, so a lane's padding can never clobber live slots
    even when its phantom positions wrap the ring capacity).  Their
    attention outputs are still computed (garbage) — callers discard them.
    """
    B, S, _ = x.shape
    hd = cfg.head_dim_
    cross = is_cross or kv_x is not None
    q = _rope(apply_dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd),
              positions, cfg)

    if cross and cache is not None and kv_x is None:
        # decode against a static (encoder) cross cache: no writes, no mask
        q = constrain(q, "b", None, "tp", None)
        ck, cv = _layer_of(cache.k, layer), _layer_of(cache.v, layer)
        cp = _layer_of(cache.positions, layer)
        if S == 1:
            out = ring_attention(q, ck, cv, positions, cp, causal=False,
                                 window=0)
        else:
            out = flash_attention(q, from_ring(ck, hd), from_ring(cv, hd),
                                  positions, cp, causal=False, window=0,
                                  chunk=cfg.attn_chunk)
        y = apply_dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
        return y, cache

    src = kv_x if kv_x is not None else x
    Skv = src.shape[1]
    k = apply_dense(p["wk"], src).reshape(B, Skv, cfg.n_kv_heads, hd)
    v = apply_dense(p["wv"], src).reshape(B, Skv, cfg.n_kv_heads, hd)

    kv_pos = positions if not cross else jnp.arange(Skv, dtype=jnp.int32)
    k = _rope(k, kv_pos, cfg)

    # Shard attention across the model axis (pspec.py):
    # "kv" shards kv heads; "repeat" duplicates kv to q-heads so the head
    # axis shards evenly (zero attention collectives at a small kv cost).
    scheme = head_scheme(cfg.n_kv_heads, cfg.n_heads)
    q = constrain(q, "b", None, "tp", None)
    g = cfg.n_heads // max(cfg.n_kv_heads, 1)

    def _spread(kk, vv):
        if scheme == "repeat" and g > 1:
            kk = jnp.repeat(kk, g, axis=2)
            vv = jnp.repeat(vv, g, axis=2)
        kk = constrain(kk, "b", None, "tp", None)
        vv = constrain(vv, "b", None, "tp", None)
        return kk, vv

    new_cache = None
    if cache is not None and not cross:
        # decode: write new kv into per-sequence ring slots, attend against
        # the whole cache.  positions may be (S,) shared or (B, S) per-slot
        # (serving pools where sequences sit at different depths).  S > 1
        # with a cache is the chunked-prefill extension path: prompt chunks
        # appended to existing rings at arbitrary per-sequence offsets —
        # batched, each row masked against its own positions.
        window = cfg.window if cfg.attn_type == "swa" else 0
        C = cache.positions.shape[-1]
        if S > C:
            # consecutive positions are only slot-distinct modulo the ring
            # capacity: a wider chunk would make two rows of the same
            # sequence scatter into one slot (nondeterministic winner)
            raise ValueError(
                f"cache extension chunk ({S} tokens) exceeds the KV ring "
                f"capacity ({C}): in-chunk positions would alias ring slots")
        with jax.named_scope("kv_ring"):
            pos_b = positions if positions.ndim == 2 \
                else jnp.broadcast_to(positions[None], (B, S))
            slots = pos_b % C                                   # (B, S)
            bidx = jnp.arange(B)[:, None]
            at = (bidx, slots) if layer is None else (layer, bidx, slots)
            if q_valid is not None:
                # ragged rows: pad entries re-write the ring's current
                # contents (slots within a row are distinct — S <= C
                # enforced above and positions are consecutive — so the
                # masked scatter is deterministic)
                kw = jnp.where(q_valid[..., None], to_ring(k), cache.k[at])
                vw = jnp.where(q_valid[..., None], to_ring(v), cache.v[at])
                pw = jnp.where(q_valid, pos_b, cache.positions[at])
            else:
                kw, vw, pw = to_ring(k), to_ring(v), pos_b
            # only the new entries are written: under a layer axis the
            # stacked rings are updated in place, never sliced out whole
            new_cache = KVCache(
                k=cache.k.at[at].set(kw.astype(cache.k.dtype)),
                v=cache.v.at[at].set(vw.astype(cache.v.dtype)),
                positions=cache.positions.at[at].set(pw))
            kl = _layer_of(new_cache.k, layer)
            vl = _layer_of(new_cache.v, layer)
            pl = _layer_of(new_cache.positions, layer)
            if S > 1 and window:
                # SWA carry-window extension: a chunk landing at offset o
                # recycles ring slots (capacity = window) that still hold
                # in-window keys needed by the chunk's own earliest queries
                # — attending against the POST-write ring would silently
                # drop them.  Attend instead against the PRE-write ring
                # CARRIED alongside the chunk's own keys: the ring holds
                # positions o-C..o-1 (a superset of every in-window key the
                # chunk can see), the chunk contributes o..o+S-1, and the
                # two position sets are disjoint, so the window mask
                # selects exactly the right keys.  Pad rows' chunk keys are
                # masked out (-1) so a short row can only see its own live
                # ring.  The RING is still written through the masked
                # scatter above — eviction there is correct (decode never
                # looks back past the window).
                kp_chunk = pos_b if q_valid is None \
                    else jnp.where(q_valid, pos_b, -1)
                kl = jnp.concatenate([_layer_of(cache.k, layer), to_ring(k)],
                                     axis=1)
                vl = jnp.concatenate([_layer_of(cache.v, layer), to_ring(v)],
                                     axis=1)
                pl = jnp.concatenate(
                    [_layer_of(cache.positions, layer), kp_chunk], axis=1)
            if S > 1:
                # the chunked scan reads token-major K/V
                kl, vl = from_ring(kl, hd), from_ring(vl, hd)
            kl = _context_parallel(kl, g, scheme, hd)
            vl = _context_parallel(vl, g, scheme, hd)
        if S == 1:
            out = ring_attention(q, kl, vl, pos_b, pl, causal=causal,
                                 window=window)
        else:
            out = flash_attention(q, kl, vl, pos_b, pl, causal=causal,
                                  window=window, chunk=cfg.attn_chunk)
    else:
        window = cfg.window if (cfg.attn_type == "swa" and not cross) else 0
        ka, va = _spread(k, v)
        if window and S > 1:
            out = swa_attention(q, ka, va, positions, kv_pos, window=window,
                                q_chunk=cfg.attn_chunk)
        elif causal and not cross and S > 2 * cfg.attn_chunk:
            out = chunked_causal_attention(q, ka, va, positions, kv_pos,
                                           chunk=cfg.attn_chunk)
        else:
            out = flash_attention(q, ka, va, positions, kv_pos,
                                  causal=causal and not cross, window=0,
                                  chunk=cfg.attn_chunk)
        if return_cache:
            with jax.named_scope("kv_ring"):
                new_cache = _build_ring(cfg, k, v, kv_pos, cross=cross,
                                        cache_len=cache_len, q_valid=q_valid)

    out = constrain(out, "b", None, "tp", None)
    y = apply_dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
    return y, new_cache


def _build_ring(cfg, k, v, kv_pos, *, cross: bool, cache_len: int | None,
                q_valid: jax.Array | None) -> KVCache:
    """The ring a prefill pass leaves, from its token-major K/V: slot =
    pos % C, the last kept positions.  The ring is sized for the TARGET
    sequence length (cache_len), not the prompt, so subsequent decode steps
    never clobber live slots."""
    B, Skv = k.shape[:2]
    C = Skv if cross else cache_capacity(cfg, cache_len or int(Skv))
    if q_valid is not None and not cross:
        # Ragged stacked prefill: the last C COLUMNS of a padded batch are
        # pads for a short row — slicing them would evict that row's real
        # in-window keys.  Build each row's ring by a per-(row, slot) GATHER
        # of its last min(C, L) VALID positions instead: slot s's owner is
        # the largest valid position congruent to s mod C.
        lengths = jnp.sum(q_valid.astype(jnp.int32), axis=1)     # (B,)
        s_idx = jnp.arange(C, dtype=jnp.int32)[None]             # (1,C)
        last = lengths[:, None] - 1                              # (B,1)
        owner = last - ((last - s_idx) % C)                      # (B,C)
        valid = (owner >= 0) & (lengths[:, None] > 0)
        col = jnp.clip(owner, 0, Skv - 1)[..., None, None]
        kb = jnp.take_along_axis(k, col, axis=1)
        vb = jnp.take_along_axis(v, col, axis=1)
        has = valid[..., None, None]
        positions = jnp.where(valid, owner, -1)
    else:
        # The kept positions p0..p0+n_keep-1 are consecutive (the pass's
        # own arange), so slot s holds kept entry (s - p0) mod C: a GATHER,
        # like the ragged branch.  The TPU compiler aborts on a scatter into
        # a zero ring in a program that also holds a decode step's ring
        # write (``generate``).
        n_keep = min(C, Skv)
        p0 = kv_pos[Skv - n_keep].astype(jnp.int32)
        j = (jnp.arange(C, dtype=jnp.int32) - p0) % C                # (C,)
        src = Skv - n_keep + jnp.minimum(j, n_keep - 1)
        kb = jnp.take(k, src, axis=1)
        vb = jnp.take(v, src, axis=1)
        has = (j < n_keep)[None, :, None, None]
        positions = jnp.broadcast_to(
            jnp.where(j < n_keep, p0 + j, -1)[None], (B, C))
    return KVCache(k=to_ring(jnp.where(has, kb, 0)),
                   v=to_ring(jnp.where(has, vb, 0)), positions=positions)


def _context_parallel(r: jax.Array, g: int, scheme: str, head_dim: int
                      ) -> jax.Array:
    """A ring, stored (B, C, Hkv * D) or token-major (B, C, Hkv, D), as
    attention reads it under a mesh: sequence-sharded (context
    parallelism).  Repeating kv heads is fine, but constraining heads onto
    the model axis here would force a full cache reshard."""
    if scheme == "repeat" and g > 1:
        r4 = r if r.ndim == 4 else from_ring(r, head_dim)
        r4 = jnp.repeat(r4, g, axis=2)
        r = r4 if r.ndim == 4 else to_ring(r4)
    return constrain(r, "b", "tp", *([None] * (r.ndim - 2)))
