"""Framework-facing ops for the digit-plane DSLOT engine.

The engine is split into a **prepare/execute** pair — the software analogue of
the paper's weight-stationary dataflow:

* ``dslot_prepare(w, ...) -> DslotWeights`` — everything that depends only on
  the weights, computed ONCE per layer per model lifetime: column-sort
  permutation (+ inverse), block geometry (``block_k`` VMEM auto-selection),
  N/K padding, and the |W| column-sum termination tables the kernel's
  chunk-aware bound reads.  Weights are stationary, so all of this is
  amortized over every subsequent request.
* ``dslot_execute(prepared, x, n_planes=...)`` — the per-request hot path:
  quantize activations (against a calibrated FIXED scale when one is stored
  in the prepared state — no data-dependent ``jnp.max`` in the hot path),
  run the kernel, dequantize.  MSDF digit planes are derived INSIDE the
  kernel from the quantized block (``ref.sd_digit_plane`` arithmetic), never
  materialized as a (D, M, K) tensor in HBM — the activation stream the
  kernel reads is the ~n_bits/8-byte-per-element ``q`` itself, not D digit
  planes of it.  ``n_planes`` is a RUNTIME argument (scalar or per-row
  vector): planes beyond it are predicated off in the Pallas kernel / masked
  in the jnp replay (per-row budgets travel as a VMEM column into the
  kernel), so changing precision never retraces — this is the paper's
  "precision tuned at run-time" as a first-class request parameter.
* ``calibrate_scale(x_sample, ...)`` — one-shot activation-range calibration;
  store the result via ``DslotWeights.with_scale``.

``dslot_matmul`` remains as the fused one-shot entry point (prepare+execute
in a single jit) used by benchmarks and ad-hoc callers; layers and the
serving engine go through the split API.  ``docs/kernel.md`` maps the kernel
to the paper; ``docs/architecture.md`` shows where this split sits in the
serving stack.

NOTE for chunked/serving use: without a calibrated ``x_scale`` the execute
path quantizes against the per-call activation max, which depends on the
token window each call sees — pin a scale (``calibrate_scale`` +
``DslotWeights.with_scale`` or ``DslotConfig.act_scale``) when results must
be invariant to how a sequence is split into calls (e.g. chunked prefill).

Backends: ``"pallas"`` (interpret on CPU, compiled on TPU; real skipped MXU
passes), ``"jnp"`` (vectorized replay with identical semantics and identical
termination statistics), ``"auto"`` (pallas on TPU, jnp elsewhere).

Beyond-paper optimization (``sort_columns=True``): weight-stationary column
reordering.  Tile termination requires *spatially clustered* dead outputs;
sorting output columns by their weight column-sum (a static, offline
permutation — exactly the paper's stationary-weight assumption) clusters
ReLU-dead neurons into contiguous tiles, which measurably raises the
skipped-pass fraction.  The inverse permutation is applied to the output, so
results are unchanged.

Tensor parallelism (``dslot_prepare(mesh=..., tp_axis=...)``): the prepared
state shards along the OUTPUT (N) axis at tile granularity across the mesh's
``tp_axis`` — the software analogue of replicating the paper's PE array.
Early termination is a per-N-tile decision and the |W| colsum termination
tables and MSR plane bounds are per-column/per-tile, so every shard runs the
SAME kernel on its own column slice with its own termination tables and no
cross-device coordination; outputs and per-tile ``planes_used`` concatenate
back (``shard_map`` with the activations replicated), and the global
``DslotStats`` accounting is computed from the reassembled arrays exactly as
in the single-device path — results and statistics are bit-identical to
``mesh=None`` (pinned by ``tests/test_tensor_parallel.py``).  When the tile
count does not divide the shard count, extra all-zero N-tiles (plane bound
0 — exact no-ops, the ``core.msr`` mechanism) pad the shard layout and are
sliced off after the gather.  See ``docs/distributed.md``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.msr import tile_plane_bound

from .dslot_matmul import (_pad_to, colsum_tables, dslot_matmul_pallas,
                           q_storage_dtype, select_block_k)
from .ref import sd_digit_plane

__all__ = ["DslotStats", "DslotWeights", "dslot_matmul", "dslot_prepare",
           "dslot_execute", "calibrate_scale", "prepare_call_count",
           "quantize_activations"]

_PREPARE_CALLS = 0


def prepare_call_count() -> int:
    """Number of ``dslot_prepare`` invocations (trace-time for jitted
    callers) since process start — tests assert prepare-once behaviour."""
    return _PREPARE_CALLS


class DslotStats(NamedTuple):
    planes_used: jax.Array      # (Mt, Nt) int32 — MXU passes per output tile
    n_planes: int               # plane budget the call was traced with
    skipped_frac: jax.Array     # scalar — fraction of plane-passes skipped
                                # (includes weight-side bounded planes: the
                                # bound caps planes_used, so activation- and
                                # weight-side savings compound here)
    row_planes_used: jax.Array | None = None  # (M,) f32 — effective planes
                                # per output row (serving: per-slot account)
    planes_bounded: jax.Array | None = None  # (Mt, Nt) int32 — planes never
                                # ISSUED because the static weight-side MSR
                                # bound capped the tile below its granted
                                # budget; disjoint from the activation-side
                                # early-termination planes_used accounting


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class DslotWeights:
    """Prepared (weight-stationary) state of one DSLOT layer.

    Array children are jit/vmap/scan-compatible; the geometry/config fields
    are pytree aux data, so passing a ``DslotWeights`` through ``jax.jit``
    makes them static automatically.
    """
    w: jax.Array                  # (Kp, Np) padded (+sorted) weights
    suffix_colsum: jax.Array      # (Kt, Np) f32 — unseen-chunk bound table
    total_colsum: jax.Array       # (1, Np) f32 — all-of-K bound table
    inv_perm: jax.Array | None    # (N,) i32 undo of column sort, or None
    x_scale: jax.Array | None     # () f32 calibrated activation step, or
                                  # None -> dynamic per-call max (fallback)
    msr_bound: jax.Array | None = None  # (Nt,) i32 static per-N-tile plane
                                  # upper bound from weight-side MSR
                                  # analysis (core.msr), or None = no cap
    # -- static geometry / config (pytree aux data) --
    n_bits: int = 8
    relu: bool = True
    signed: bool = False
    block_m: int = 128
    block_n: int = 128
    block_k: int = 0              # resolved chunk size (never None here)
    backend: str = "jnp"          # resolved: "pallas" | "jnp"
    d_in: int = 0                 # K before padding
    d_out: int = 0                # N before padding
    mesh: Mesh | None = None      # tensor-parallel device mesh, or None =
                                  # single-device execution
    tp_axis: str = "model"        # mesh axis the N (output) tiles shard over

    def tree_flatten(self):
        children = (self.w, self.suffix_colsum, self.total_colsum,
                    self.inv_perm, self.x_scale, self.msr_bound)
        aux = (self.n_bits, self.relu, self.signed, self.block_m,
               self.block_n, self.block_k, self.backend, self.d_in,
               self.d_out, self.mesh, self.tp_axis)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    @property
    def tp_shards(self) -> int:
        """Tensor-parallel shard count (1 when unsharded)."""
        return 1 if self.mesh is None else int(self.mesh.shape[self.tp_axis])

    def with_scale(self, x_scale) -> "DslotWeights":
        """Attach a calibrated activation scale (see ``calibrate_scale``)."""
        return dataclasses.replace(
            self, x_scale=jnp.asarray(x_scale, jnp.float32))


def quantize_activations(x: jax.Array, n_bits: int = 8, signed: bool = False,
                         scale: jax.Array | None = None
                         ) -> tuple[jax.Array, jax.Array]:
    """Symmetric activation quantization -> (q int32, step float32).

    ``scale=None`` derives the step from this batch's max (data-dependent —
    fine for one-shot calls, a hot-path sync for serving); a calibrated
    fixed ``scale`` skips the reduction and clips outliers instead.
    """
    qmax = float(2 ** n_bits - 1 if not signed else 2 ** (n_bits - 1) - 1)
    if scale is None:
        amax = jnp.maximum(jnp.max(jnp.abs(x)) if signed else jnp.max(x),
                           1e-12)
        step = amax / qmax
    else:
        step = jnp.asarray(scale, jnp.float32)
    lo = -qmax if signed else 0.0
    q = jnp.clip(jnp.round(x / step), lo, qmax).astype(jnp.int32)
    return q, step


def calibrate_scale(x_sample: jax.Array, n_bits: int = 8,
                    signed: bool = False) -> jax.Array:
    """Fixed activation quantization step from a calibration batch."""
    qmax = float(2 ** n_bits - 1 if not signed else 2 ** (n_bits - 1) - 1)
    amax = jnp.max(jnp.abs(x_sample)) if signed else jnp.max(x_sample)
    return (jnp.maximum(amax, 1e-12) / qmax).astype(jnp.float32)


def dslot_prepare(w: jax.Array, *, n_bits: int = 8, relu: bool = True,
                  signed: bool = False, sort_columns: bool = False,
                  block_m: int = 128, block_n: int = 128,
                  block_k: int | None = None, backend: str = "auto",
                  x_scale: jax.Array | None = None,
                  msr_bound: bool = True, mesh: Mesh | None = None,
                  tp_axis: str = "model") -> DslotWeights:
    """One-time weight lowering: sort, pad, pick ``block_k``, build the
    termination tables and the weight-side MSR plane bound.  Call once per
    layer; reuse across every request.

    ``w``: (K, N) float32/bfloat16.  For a stacked weight (L, K, N) use
    ``jax.vmap(lambda wl: dslot_prepare(wl, ...))`` — all children map.

    ``msr_bound=True`` profiles the padded/sorted weight tiles
    (``core.msr.tile_plane_bound``) and bakes a static per-N-tile plane
    upper bound into the prepared state: tiles proven output-inert from the
    weight side alone (exactly-zero columns — including every N-padding
    tile — and, under unsigned+ReLU, all-non-positive tiles) get bound 0
    and are never issued by any backend.  Only output-exact bounds are
    emitted, so results are bit-identical to ``msr_bound=False``.

    ``mesh``/``tp_axis`` make every subsequent ``dslot_execute`` run
    tensor-parallel: N tiles shard across ``mesh.shape[tp_axis]`` devices
    under ``shard_map``, each shard terminating against its own slice of
    the colsum tables and MSR bounds (see the module docstring).  Results
    are bit-identical to ``mesh=None``.
    """
    global _PREPARE_CALLS
    _PREPARE_CALLS += 1
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "jnp"
    if mesh is not None and tp_axis not in mesh.axis_names:
        raise ValueError(
            f"tp_axis {tp_axis!r} not in mesh axes {mesh.axis_names}")
    K, N = w.shape

    inv_perm = None
    if sort_columns:
        perm = jnp.argsort(jnp.sum(w, axis=0))          # dead cols first
        w = w[:, perm]
        inv_perm = jnp.argsort(perm)

    bk = block_k or select_block_k(K, block_m, block_n, w.dtype.itemsize,
                                   q_storage_dtype(n_bits, signed).itemsize)
    w_p = _pad_to(w, block_n, axis=1)
    w_p = _pad_to(w_p, bk, axis=0)

    suffix_colsum, total_colsum = colsum_tables(w_p, bk)
    bound = tile_plane_bound(w_p, block_n, n_bits=n_bits, relu=relu,
                             signed=signed) if msr_bound else None

    return DslotWeights(
        w=w_p, suffix_colsum=suffix_colsum, total_colsum=total_colsum,
        inv_perm=inv_perm, x_scale=x_scale, msr_bound=bound, n_bits=n_bits,
        relu=relu, signed=signed, block_m=block_m, block_n=block_n,
        block_k=bk, backend=backend, d_in=K, d_out=N, mesh=mesh,
        tp_axis=tp_axis)


# ------------------------------------------------------------- execution

def _jnp_path(q: jax.Array, w: jax.Array, n_bits: int, n_planes: int,
              relu: bool, block_m: int, block_n: int, bk: int,
              suffix: jax.Array, total: jax.Array, npl: jax.Array,
              row_budget: jax.Array, tile_bound: jax.Array):
    """Reference evaluation + termination accounting, plane-free.

    Computes every plane (no skipping — this is CPU) but derives the exact
    per-tile ``planes_used`` the Pallas kernel would report, by replaying the
    chunk-aware bound check in the kernel's (plane outer, K-chunk inner)
    iteration order.  Digit planes are never stacked: each scan step derives
    plane ``d`` of its K chunk from the quantized activations on the fly
    (``ref.sd_digit_plane``, inlined on the pre-split sign/magnitude), so
    peak activation memory is O(M*K) — not O(D*M*K) — and stays at O(M*N)
    per step regardless of how small ``bk`` is (only the per-step per-tile
    dead flags are stacked).

    ``npl`` is the runtime precision (i32 scalar): planes at d >= npl
    contribute nothing and ``planes_used`` is clamped to it — the same
    semantics as the kernel's predicated passes.  ``row_budget`` ((M,) i32)
    zeroes each row's digits beyond its own budget — identical to the
    kernel's per-row budget column.  ``tile_bound`` ((Nt,) i32) is the
    static weight-side MSR plane bound: columns of tile j accumulate
    nothing at d >= tile_bound[j] and the tile's planes_used is capped by
    it — the mirror of the kernel's per-j SMEM bound scalar (a frozen tile
    whose stale termination check fires in the replay is indistinguishable
    after the cap, same as the npl clamp below).

    q (M, Kp) integer pre-padded; w (Kp, N); suffix (Kt, N) and total (N,)
    are the prepared |W| column-sum bound tables; n_planes is the static
    plane-axis depth D.
    """
    M, K = q.shape
    D = n_planes
    N = w.shape[1]
    Kt = K // bk
    Mt, Nt = M // block_m, N // block_n
    npl_f = npl.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    w_chunks = wf.reshape(Kt, bk, N)
    # K-chunk-major activation layout at its narrow storage width: the only
    # activation tensor the scan streams is (Kt, M, bk) = M*K elements, no D
    # factor — sign/magnitude are split per step on the resident chunk
    q_chunks = q.reshape(M, Kt, bk).transpose(1, 0, 2)
    scales = jnp.exp2(jnp.asarray(n_bits - 1, jnp.float32)
                      - jnp.arange(D, dtype=jnp.float32))
    step_scale = jnp.repeat(scales, Kt)                         # (D*Kt,)

    # Remaining-contribution bound after step (d, c):
    # scale_d * suffix_colsum[c] + (scale_d - 2^(n_bits - npl)) * total.
    tail = jnp.exp2(jnp.asarray(n_bits, jnp.float32) - npl_f)
    step_rem = (scales[:, None, None] * suffix[None, :, :]
                + ((scales - tail)[:, None, None]
                   * total[None, None, :])).reshape(D * Kt, N)

    bound_cols = jnp.repeat(tile_bound.astype(jnp.int32), block_n,
                            total_repeat_length=N)              # (N,)

    def body(acc, step):
        d, c, scale, rem = step
        qc = jax.lax.dynamic_index_in_dim(q_chunks, c, keepdims=False)
        # on-the-fly digit (the pinned shared arithmetic), with rows past
        # their budget (and planes past npl <= max budget) zeroed
        digit = sd_digit_plane(qc, n_bits, d).astype(jnp.float32) \
            * (row_budget > d).astype(jnp.float32)[:, None]
        wc = jax.lax.dynamic_index_in_dim(w_chunks, c, keepdims=False)
        # weight-side MSR bound: columns of a tile whose static plane bound
        # is exhausted freeze — the kernel's per-j SMEM bound predicate
        contrib = scale * jnp.dot(digit, wc,
                                  preferred_element_type=jnp.float32)
        acc = acc + contrib * (bound_cols > d).astype(jnp.float32)[None, :]
        bound = acc + rem[None, :]
        dead = jnp.all(bound.reshape(Mt, block_m, Nt, block_n) < 0.0,
                       axis=(1, 3))                             # (Mt, Nt)
        return acc, dead

    d_idx = jnp.repeat(jnp.arange(D), Kt)                       # plane per step
    c_idx = jnp.tile(jnp.arange(Kt), D)                         # w chunk per step
    acc, dead_after = jax.lax.scan(
        body, jnp.zeros((M, N), jnp.float32),
        (d_idx, c_idx, step_scale, step_rem))
    out = jnp.maximum(acc, 0.0) if relu else acc
    if relu:
        # only bound checks at steps the kernel actually enters (d < npl)
        # count; later (masked) steps can fire the stale bound spuriously,
        # but min() with npl makes them indistinguishable from no-fire.
        ever = jnp.any(dead_after, axis=0)
        first = jnp.argmax(dead_after, axis=0)                  # 0-based step
        used = jnp.where(ever, first // Kt + 1, D).astype(jnp.int32)
    else:
        used = jnp.full((Mt, Nt), D, jnp.int32)
    # a tile never runs past its weight-side bound (the kernel only counts
    # planes it actually enters); the npl clamp handles stale fires beyond
    used = jnp.minimum(used, tile_bound.astype(jnp.int32)[None, :])
    return out, jnp.minimum(used, npl.astype(jnp.int32))


def _run_backend(cfg: DslotWeights, q_p: jax.Array, w: jax.Array,
                 suffix: jax.Array, total: jax.Array, npl_scalar: jax.Array,
                 bud_p: jax.Array, bnd: jax.Array, D: int
                 ) -> tuple[jax.Array, jax.Array]:
    """One backend invocation on (a shard of) the prepared weights.

    ``w``/``suffix``/``total``/``bnd`` may be the full prepared arrays or a
    device-local N slice of them — both backends are column-independent, so
    the same code serves the single-device path and each shard_map body.
    Returns padded ``(out (Mp, N), planes_used (Mt, Nt))``.
    """
    if cfg.backend == "pallas":
        out_p, used = dslot_matmul_pallas(
            q_p, w, n_bits=cfg.n_bits, n_planes=D, relu=cfg.relu,
            block_m=cfg.block_m, block_n=cfg.block_n, block_k=cfg.block_k,
            n_planes_rt=npl_scalar, row_budget=bud_p,
            suffix_colsum=suffix, total_colsum=total,
            plane_bound=bnd)
        return out_p, jnp.minimum(used, npl_scalar.astype(jnp.int32))
    return _jnp_path(q_p, w, cfg.n_bits, D, cfg.relu,
                     cfg.block_m, cfg.block_n, cfg.block_k,
                     suffix, total[0], npl_scalar, bud_p, bnd)


def _sharded_exec(cfg: DslotWeights, q_p: jax.Array, npl_scalar: jax.Array,
                  bud_p: jax.Array, bnd: jax.Array, D: int
                  ) -> tuple[jax.Array, jax.Array]:
    """Tensor-parallel execute: N tiles shard over ``cfg.mesh[cfg.tp_axis]``.

    Activations (and the per-row budget / runtime precision scalar) are
    replicated; the prepared weight columns, colsum termination tables and
    per-tile MSR bounds split along N at tile granularity, so each device
    runs the identical kernel on its slice with its own termination state.
    When ``Nt`` does not divide the shard count, the layout is padded with
    all-zero tiles carrying plane bound 0 — exact no-ops by the ``core.msr``
    mechanism — and the pad is sliced off after the out_specs gather.
    Bit-identical to the unsharded path (both backends are column-
    independent); per-shard ``planes_used`` concatenates into the same
    global (Mt, Nt) table the stats reduction already consumes.
    """
    mesh, axis = cfg.mesh, cfg.tp_axis
    shards = int(mesh.shape[axis])
    Np = cfg.w.shape[1]
    Nt = Np // cfg.block_n
    Nt_pad = -(-Nt // shards) * shards
    extra = (Nt_pad - Nt) * cfg.block_n
    w_s = jnp.pad(cfg.w, [(0, 0), (0, extra)])
    sfx_s = jnp.pad(cfg.suffix_colsum, [(0, 0), (0, extra)])
    tot_s = jnp.pad(cfg.total_colsum, [(0, 0), (0, extra)])
    bnd_s = jnp.pad(bnd, (0, Nt_pad - Nt))      # pad tiles: bound 0 = inert

    def body(w_l, sfx_l, tot_l, bnd_l, q_l, bud_l, npl_l):
        return _run_backend(cfg, q_l, w_l, sfx_l, tot_l, npl_l, bud_l,
                            bnd_l, D)

    in_specs = (P(None, axis), P(None, axis), P(None, axis), P(axis),
                P(), P(), P())
    out_specs = (P(None, axis), P(None, axis))
    # the pallas backend has no replication rule, so the static vma checker
    # is disabled (outputs are genuinely axis-sharded anyway)
    sm = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    out_p, used = sm(w_s, sfx_s, tot_s, bnd_s, q_p, bud_p, npl_scalar)
    # gather the N shards back to every device before the pad slice and the
    # un-sort gather: on an explicit-axis mesh (``jax.make_mesh``'s default)
    # neither may index a sharded dimension, and both read across shards
    full = NamedSharding(mesh, P())
    out_p, used = (jax.sharding.reshard(a, full) for a in (out_p, used))
    return out_p[:, :Np], used[:, :Nt]


def _execute_core(prepared: DslotWeights, x: jax.Array, npl: jax.Array,
                  static_planes: int | None = None
                  ) -> tuple[jax.Array, DslotStats]:
    """Shared execute path.  ``npl`` is i32, scalar or per-row (M,).

    ``static_planes`` (fused one-shot path only) additionally shrinks the
    kernel grid's plane axis to a STATIC depth — the split path keeps the
    grid at ``n_bits`` and predicates instead, trading a few empty grid
    steps for zero retraces.

    No digit-plane tensor is built here: the quantized activations go to the
    backends as-is (at the narrowest integer width that holds them) and each
    backend derives digit planes on the fly — the paper's online generation,
    not an HBM-materialized encoding.  Per-row budgets ride along as a
    runtime vector consumed inside the kernel (VMEM per-M-tile) / scan.
    """
    cfg = prepared
    M, K = x.shape
    assert K == cfg.d_in, (x.shape, cfg.d_in)

    q, step = quantize_activations(x, n_bits=cfg.n_bits, signed=cfg.signed,
                                   scale=cfg.x_scale)
    D = min(static_planes or cfg.n_bits, cfg.n_bits)

    if npl.ndim == 1:
        row_budget = jnp.clip(npl, 1, D)
        npl_scalar = jnp.max(row_budget)
        budget_f = row_budget.astype(jnp.float32)
    else:
        row_budget = None
        npl_scalar = jnp.clip(npl, 1, D)
        budget_f = npl_scalar.astype(jnp.float32)

    q_p = _pad_to(q.astype(q_storage_dtype(cfg.n_bits, cfg.signed)),
                  cfg.block_m, axis=0)
    if q_p.shape[1] < cfg.w.shape[0]:           # match prepared K padding
        q_p = jnp.pad(q_p, [(0, 0), (0, cfg.w.shape[0] - q_p.shape[1])])
    Mp = q_p.shape[0]
    # per-row budget over the padded rows (pad rows: zero budget = all-zero
    # digits, same as the old zero plane padding); scalar budgets broadcast
    bud_p = jnp.full((Mp,), npl_scalar, jnp.int32) if row_budget is None \
        else jnp.pad(row_budget.astype(jnp.int32), (0, Mp - M))

    Nt = cfg.w.shape[1] // cfg.block_n
    bnd = jnp.full((Nt,), D, jnp.int32) if cfg.msr_bound is None \
        else jnp.minimum(cfg.msr_bound.astype(jnp.int32), D)

    if cfg.mesh is not None:
        out_p, used = _sharded_exec(cfg, q_p, npl_scalar, bud_p, bnd, D)
    else:
        out_p, used = _run_backend(cfg, q_p, cfg.w, cfg.suffix_colsum,
                                   cfg.total_colsum, npl_scalar, bud_p,
                                   bnd, D)

    out = out_p[:M, :cfg.d_out] * step
    if cfg.inv_perm is not None:
        out = out[:, cfg.inv_perm]

    # per-row effective planes: tile usage spread over its rows, clipped to
    # each row's own budget — the per-request energy account for serving.
    rows_used = jnp.repeat(used.astype(jnp.float32).mean(axis=1),
                           cfg.block_m, total_repeat_length=used.shape[0]
                           * cfg.block_m)[:M]
    if row_budget is not None:
        rows_used = jnp.minimum(rows_used, budget_f)
        skipped = 1.0 - jnp.mean(rows_used) / jnp.maximum(
            jnp.mean(budget_f), 1.0)
    else:
        skipped = 1.0 - jnp.mean(used.astype(jnp.float32)) / budget_f
    # weight-side never-issued planes: the static MSR bound capped tile j
    # below the call's granted budget — the same for every M-tile/row, so
    # it broadcasts; skipped_frac above already compounds with it (the
    # bound caps planes_used), this field attributes the static share.
    bounded = jnp.broadcast_to(
        jnp.maximum(npl_scalar.astype(jnp.int32) - bnd, 0)[None, :],
        used.shape)
    return out, DslotStats(planes_used=used, n_planes=D,
                           skipped_frac=skipped, row_planes_used=rows_used,
                           planes_bounded=bounded)


@jax.jit
def _dslot_execute_jit(prepared: DslotWeights, x: jax.Array, npl: jax.Array
                       ) -> tuple[jax.Array, DslotStats]:
    return _execute_core(prepared, x, npl)


def dslot_execute(prepared: DslotWeights, x: jax.Array, *,
                  n_planes=None) -> tuple[jax.Array, DslotStats]:
    """Per-request execution against prepared weights: ``[relu](x @ w)``.

    ``x``: (M, d_in) float activations.
    ``n_planes``: runtime precision — None (full ``n_bits``), a python int /
    i32 scalar, or a per-row (M,) i32 vector (serving: one budget per slot).
    Runtime values share one trace; only the scalar/vector distinction (and
    new shapes) retraces.
    """
    if n_planes is None:
        n_planes = prepared.n_bits
    npl = jnp.asarray(n_planes, jnp.int32)
    return _dslot_execute_jit(prepared, x, npl)


@functools.partial(jax.jit, static_argnames=(
    "n_bits", "n_planes", "relu", "block_m", "block_n", "block_k", "backend",
    "sort_columns", "signed"))
def _dslot_matmul_fused(x: jax.Array, w: jax.Array, *, n_bits: int = 8,
                        n_planes: int | None = None, relu: bool = True,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int | None = None,
                        backend: str = "auto", sort_columns: bool = False,
                        signed: bool = False
                        ) -> tuple[jax.Array, DslotStats]:
    D = min(n_planes or n_bits, n_bits)
    prepared = dslot_prepare(
        w, n_bits=n_bits, relu=relu, signed=signed,
        sort_columns=sort_columns, block_m=block_m, block_n=block_n,
        block_k=block_k, backend=backend)
    return _execute_core(prepared, x, jnp.asarray(D, jnp.int32),
                         static_planes=D)


def dslot_matmul(x: jax.Array, w: jax.Array, *, n_bits: int = 8,
                 n_planes: int | None = None, relu: bool = True,
                 block_m: int = 128, block_n: int = 128,
                 block_k: int | None = None,
                 backend: str = "auto", sort_columns: bool = False,
                 signed: bool = False
                 ) -> tuple[jax.Array, DslotStats]:
    """Fused one-shot digit-serial matmul: prepare + execute in one jit.

    Kept for benchmarks and ad-hoc calls; layers and serving use the split
    ``dslot_prepare``/``dslot_execute`` so weight lowering is amortized.
    ``n_planes`` here is STATIC (the kernel grid shrinks); use
    ``dslot_execute`` for runtime precision.

    Weight-side grid trim: since ``n_planes`` is static here, a concrete
    ``w`` whose global MSR plane bound is below ``n_bits`` (every column
    output-inert — the bound is a per-column property, invariant under the
    prepare-time sort/pad) shrinks the static plane axis itself, not just
    the per-tile predicate (clamped to one plane: the grid cannot be
    empty, and planes beyond a tile's bound are exact no-ops).  Traced
    callers (``w`` under jit) skip the eager check and rely on the
    per-tile SMEM bound inside the kernel.
    """
    D = min(n_planes or n_bits, n_bits)
    if not isinstance(w, jax.core.Tracer):
        import numpy as np
        wn = np.asarray(jax.device_get(w))
        inert = (wn == 0.0).all(axis=0)
        if relu and not signed:
            inert |= (wn <= 0.0).all(axis=0)
        if bool(inert.all()):
            D = 1
    return _dslot_matmul_fused(
        x, w, n_bits=n_bits, n_planes=D, relu=relu, block_m=block_m,
        block_n=block_n, block_k=block_k, backend=backend,
        sort_columns=sort_columns, signed=signed)
