"""Mamba2 (SSD — state-space duality) mixer block [arXiv:2405.21060].

Recurrence per head (state N, head dim P):
    h_t = exp(dt_t * A) * h_{t-1} + dt_t * (B_t ⊗ x_t)
    y_t = C_t · h_t + D_skip * x_t

Training/prefill uses the chunked SSD form: intra-chunk contributions via the
masked decay matrix L = exp(segsum(a)) (quadratic only within a chunk), chunk
states propagated with a sequential scan over chunks — O(S * Q) compute and
memory, sub-quadratic in S (this is why mamba2 runs the ``long_500k`` cell).
Decode is the O(1)-per-token recurrence on a persistent (H, P, N) state.

Chunked and sequential paths are tested equal to ~1e-4 (float accumulation).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .layers import Params


class SSMState(NamedTuple):
    conv: jax.Array    # (B, k-1, conv_channels) — causal conv tail
    ssm: jax.Array     # (B, H, P, N) — recurrent state


def _dims(cfg):
    """(d_inner, heads H, head dim P, state N, groups G): H heads of P share
    G groups of B and C, head h the group h // (H / G).  H * P need not be
    ``ssm_expand * d_model`` where ``ssm_heads`` says how many heads."""
    P = cfg.ssm_headdim
    H = cfg.ssm_heads or cfg.ssm_expand * cfg.d_model // P
    return H * P, H, P, cfg.ssm_state, cfg.ssm_groups


def init_ssm(cfg, key) -> Params:
    dt = jnp.dtype(cfg.dtype)
    d_inner, H, P, N, G = _dims(cfg)
    conv_ch = d_inner + 2 * G * N
    in_dim = 2 * d_inner + 2 * G * N + H
    ks = jax.random.split(key, 4)
    return {
        "w_in": (jax.random.normal(ks[0], (cfg.d_model, in_dim), jnp.float32)
                 * cfg.d_model ** -0.5).astype(dt),
        "conv_w": (jax.random.normal(ks[1], (cfg.ssm_conv, conv_ch),
                                     jnp.float32) * 0.2).astype(dt),
        "conv_b": jnp.zeros((conv_ch,), dt),
        "A_log": jnp.zeros((H,), jnp.float32),          # A = -exp(A_log) = -1
        "D_skip": jnp.ones((H,), jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "norm_scale": jnp.ones((d_inner,), jnp.float32),
        "w_out": (jax.random.normal(ks[2], (d_inner, cfg.d_model), jnp.float32)
                  * d_inner ** -0.5).astype(dt),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array,
                 tail: jax.Array | None, lengths: jax.Array | None = None
                 ) -> tuple[jax.Array, jax.Array]:
    """Depthwise causal conv1d.  x: (B,S,C), w: (k,C).  Returns (y, new_tail).

    ``lengths`` (B,) marks ragged rows right-padded to S: the returned tail
    is then each row's last k-1 VALID inputs (a per-row gather into
    ``concat([tail, x])``) instead of the last k-1 columns — a short row's
    pad columns must never enter its carried conv window.  A length-0 row's
    tail is its incoming tail, unchanged.
    """
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([tail, x], axis=1)             # (B, S+k-1, C)
    idx = jnp.arange(x.shape[1])[:, None] + jnp.arange(k)[None, :]
    windows = xp[:, idx]                                # (B, S, k, C)
    y = jnp.einsum("bskc,kc->bsc", windows, w) + b
    if lengths is None:
        new_tail = xp[:, xp.shape[1] - (k - 1):]
    else:
        # xp index of position t is t + (k-1); the k-1 window ending at a
        # row's last valid input starts at index L (identity when L == 0)
        tidx = lengths[:, None].astype(jnp.int32) \
            + jnp.arange(k - 1, dtype=jnp.int32)[None]
        new_tail = jnp.take_along_axis(xp, tidx[..., None], axis=1)
    return jax.nn.silu(y), new_tail


def _segsum(a: jax.Array) -> jax.Array:
    """a: (..., Q) log decays -> (..., Q, Q) with S[i,j]=sum_{j<m<=i} a_m,
    -inf above the diagonal."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    S = cs[..., :, None] - cs[..., None, :]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(tri, S, -jnp.inf)


def _groups(M: jax.Array) -> jax.Array:
    """B or C as (b, s, g, n): a (b, s, n) array is one group."""
    return M[:, :, None] if M.ndim == 3 else M


def ssd_chunked(x, dtv, A, B, C, chunk: int, init_state=None):
    """Chunked SSD.  x:(b,s,h,p) dtv:(b,s,h) A:(h,) B,C:(b,s,g,n) or
    (b,s,n) for one group; head i reads group i // (h / g).
    Returns y:(b,s,h,p), final_state:(b,h,p,n)."""
    B, C = _groups(B), _groups(C)
    b, s, h, pdim = x.shape
    g, n = B.shape[-2:]
    k = h // g                                          # heads per group
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtv = jnp.pad(dtv, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    a = (dtv * A[None, None, :]).astype(jnp.float32)    # (b, s', h) log decay

    xc = x.reshape(b, nc, q, g, k, pdim).astype(jnp.float32)
    dc = dtv.reshape(b, nc, q, g, k).astype(jnp.float32)
    ac = jnp.moveaxis(a.reshape(b, nc, q, g, k), 2, -1)  # (b, nc, g, k, q)
    Bc = B.reshape(b, nc, q, g, n).astype(jnp.float32)
    Cc = C.reshape(b, nc, q, g, n).astype(jnp.float32)

    cs = jnp.cumsum(ac, axis=-1)                        # inclusive
    L = jnp.exp(_segsum(ac))                            # (b, nc, g, k, q, q)

    # intra-chunk: y_i += sum_{j<=i} C_i·B_j L[i,j] dt_j x_j
    scores = jnp.einsum("bcign,bcjgn->bcgij", Cc, Bc)   # (b, nc, g, q, q)
    w = scores[:, :, :, None] * L                       # (b, nc, g, k, q, q)
    y_intra = jnp.einsum("bcgkij,bcjgk,bcjgkp->bcigkp", w, dc, xc)

    # chunk states: sum_j decay_to_end[j] dt_j B_j x_j  -> (b,nc,g,k,p,n)
    decay_end = jnp.exp(cs[..., -1:] - cs)              # (b, nc, g, k, q)
    states = jnp.einsum("bcgkj,bcjgk,bcjgkp,bcjgn->bcgkpn",
                        decay_end, dc, xc, Bc)

    # inter-chunk recurrence over nc
    T = jnp.exp(cs[..., -1])                            # (b, nc, g, k)
    h0 = (jnp.zeros((b, h, pdim, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32)).reshape(b, g, k, pdim, n)

    def body(hprev, inp):
        Tc, sc = inp
        hnew = Tc[..., None, None] * hprev + sc
        return hnew, hprev

    (hfin, hprevs) = jax.lax.scan(
        body, h0, (jnp.moveaxis(T, 1, 0), jnp.moveaxis(states, 1, 0)))
    hprevs = jnp.moveaxis(hprevs, 0, 1)                 # (b,nc,g,k,p,n)

    # inter-chunk output: y_i += C_i · decay_in[i] · h_prev
    decay_in = jnp.exp(cs)                              # includes a_i
    y_inter = jnp.einsum("bcign,bcgki,bcgkpn->bcigkp", Cc, decay_in, hprevs)

    y = (y_intra + y_inter).reshape(b, nc * q, h, pdim)[:, :s]
    return y, hfin.reshape(b, h, pdim, n)


def ssd_sequential(x, dtv, A, B, C, init_state=None):
    """Naive O(S) sequential recurrence — oracle for tests and decode.
    Shapes as ``ssd_chunked``."""
    B, C = _groups(B), _groups(C)
    b, s, h, pdim = x.shape
    g, n = B.shape[-2:]
    k = h // g
    h0 = (jnp.zeros((b, h, pdim, n), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32)).reshape(b, g, k, pdim, n)

    def step(hprev, inp):
        xt, dt_t, Bt, Ct = inp
        at = jnp.exp(dt_t * A.reshape(g, k))            # (b, g, k)
        upd = jnp.einsum("bgk,bgkp,bgn->bgkpn", dt_t, xt, Bt)
        hnew = at[..., None, None] * hprev + upd
        yt = jnp.einsum("bgn,bgkpn->bgkp", Ct, hnew)
        return hnew, yt

    xs = (jnp.moveaxis(x.astype(jnp.float32).reshape(b, s, g, k, pdim), 1, 0),
          jnp.moveaxis(dtv.astype(jnp.float32).reshape(b, s, g, k), 1, 0),
          jnp.moveaxis(B.astype(jnp.float32), 1, 0),
          jnp.moveaxis(C.astype(jnp.float32), 1, 0))
    hfin, ys = jax.lax.scan(step, h0, xs)
    return (jnp.moveaxis(ys, 0, 1).reshape(b, s, h, pdim),
            hfin.reshape(b, h, pdim, n))


def apply_ssm(p: Params, x: jax.Array, cfg, state: SSMState | None = None,
              return_state: bool = False, sequential: bool = False,
              q_valid: jax.Array | None = None
              ) -> tuple[jax.Array, SSMState | None]:
    """Full mamba2 mixer.  x: (B, S, d_model).

    ``q_valid`` (B, S) bool marks ragged rows right-padded to S.  Pad
    positions are exact IDENTITY steps of the recurrence — ``dt = 0`` gives
    decay ``exp(0) = 1`` and a zero state update in both the sequential and
    chunked SSD paths — and the conv tail gathers each row's last valid
    inputs, so carried state only ever advances past real tokens (pad rows'
    emitted outputs are garbage; callers discard them).
    """
    B_, S, _ = x.shape
    d_inner, H, P, N, G = _dims(cfg)
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["w_in"], preferred_element_type=x.dtype)
    z, xBC, dt_raw = jnp.split(
        zxbcdt, [d_inner, d_inner + d_inner + 2 * G * N], axis=-1)

    lengths = None if q_valid is None \
        else jnp.sum(q_valid.astype(jnp.int32), axis=1)
    conv_tail = state.conv if state is not None else None
    with jax.named_scope("conv"):
        xBC, new_tail = _causal_conv(xBC, p["conv_w"], p["conv_b"],
                                     conv_tail, lengths=lengths)
    x_ssm, Bmat, Cmat = jnp.split(xBC, [d_inner, d_inner + G * N], axis=-1)

    with jax.named_scope("scan"):
        dtv = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
        if q_valid is not None:
            dtv = jnp.where(q_valid[..., None], dtv, 0.0)
        A = -jnp.exp(p["A_log"])
        xh = x_ssm.reshape(B_, S, H, P)
        Bmat, Cmat = Bmat.reshape(B_, S, G, N), Cmat.reshape(B_, S, G, N)

        init = state.ssm if state is not None else None
        if sequential or S == 1:
            y, hfin = ssd_sequential(xh, dtv, A, Bmat, Cmat, init_state=init)
        else:
            y, hfin = ssd_chunked(xh, dtv, A, Bmat, Cmat, cfg.ssm_chunk,
                                  init_state=init)
        y = y + p["D_skip"][None, None, :, None] * xh.astype(jnp.float32)

    with jax.named_scope("out"):
        # gated RMSNorm (mamba2): norm(y * silu(z)) * scale, over each of
        # the G groups of d_inner / G channels
        g = y.reshape(B_, S, G, d_inner // G) * jax.nn.silu(
            z.astype(jnp.float32)).reshape(B_, S, G, d_inner // G)
        r = jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.norm_eps)
        g = (g * r).reshape(B_, S, d_inner) * p["norm_scale"]
        out = jnp.einsum("bse,ed->bsd", g.astype(x.dtype), p["w_out"],
                         preferred_element_type=x.dtype)
    new_state = SSMState(conv=new_tail, ssm=hfin) if return_state else None
    return out, new_state
