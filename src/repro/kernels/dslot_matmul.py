"""Pallas TPU kernel: digit-serial MSDF matmul with fused in-kernel digit
encoding and per-tile early termination.

TPU-native adaptation of DSLOT-NN's datapath (``docs/kernel.md``).  The FPGA
design streams one signed digit per cycle through online multipliers and kills
a SOP the moment its MSDF prefix goes negative.  A TPU has no per-lane early
exit, so the unit of "digit" becomes a *digit plane* (one MXU matmul) and the
unit of termination becomes an *output tile*:

    C = sum_d 2^(n-1-d) * (P_d @ W),      P_d in {-1,0,1}^(M x K), d MSDF

Like the paper's engine — and unlike the first port — the digit planes are
never materialized in HBM.  The kernel input is the quantized activation
block ``q`` itself ((M, K) integer, |q| < 2^n_bits); each grid step derives
plane ``d`` of the resident VMEM chunk arithmetically (sign-magnitude
recoding: bit ``n_bits-1-d`` of |q| times sign(q) — the same digits
``ref.make_planes`` produces, one plane at a time).  That removes the
(D, M, K) plane tensor (an up-to-8x inflation of the activation stream that
had to be written to and re-read from HBM once per plane) and means
predicated-off planes and terminated tiles skip their encode work for free:
a digit that is never consumed is never computed.

Weights stream through VMEM in ``block_k`` chunks (grid axis ``c``), so ``K``
is no longer bounded by what fits in VMEM at once.  After accumulating
(plane d, chunk c) the remaining work can contribute at most

    R[d, c][n] = 2^(n-1-d) * S_c[n]  +  (2^(n-1-d) - 2^(n-npl)) * T[n]

to output column n, where ``S_c`` is the |W| column-sum over the K chunks not
yet seen in the current plane, ``T`` the |W| column-sum over ALL of K, and
``npl`` the runtime precision (digits are bounded by 1 in magnitude; the
second term is the geometric sum of the unseen planes).  ``R`` decreases
monotonically along the (d, c) iteration order, so a tile with
``max_m(acc + R) < 0`` everywhere is *provably* negative under ReLU at the
earliest chunk that observes it: its remaining MXU passes (and digit
extraction) are SKIPPED (predicated with ``pl.when``) and it emits zeros —
the tile-granular Algorithm 1, now chunk-aware.  At the last chunk of a plane
``S_c = 0`` and the bound coincides with the untiled kernel's, so tiling can
only terminate a tile at the same plane or an earlier one.

Runtime precision is two-level: ``n_planes_rt`` (i32 scalar in SMEM)
predicates whole planes off for the entire call, and ``row_budget`` (i32
per-row vector, one ``(block_m, 1)`` VMEM block per M-tile) zeroes digits
beyond each row's own budget inside the extraction — per-request precision
in a serving batch without masking work outside the kernel.  Both are
runtime values: changing precision never retraces.

Grid/layout: ``grid = (M/bm, N/bn, D, K/bk)`` with the digit-plane and
K-chunk axes innermost (sequential, "arbitrary" semantics); the f32
accumulator and the termination flag live in VMEM/SMEM scratch that persists
across the (d, c) axes.  The ``q`` block index is ``(i, c)`` — independent
of the plane axis — so when the whole (padded) K fits one chunk (the common
``select_block_k`` outcome) the chunk stays resident across all D planes and
activations are read from HBM ONCE per (i, j) tile instead of D times.
Blocks are MXU-aligned on real TPU (bm, bn multiples of 128, bk a multiple
of 128 when tiled; any size in interpret mode).  ``block_k=None`` picks the
largest K chunk that keeps the working set inside the VMEM budget — there is
no whole-K residency requirement anymore.

Weights may be float32 or bfloat16 (accumulation is always f32).  Quantized
activations are stored at the narrowest integer width that holds the
quantization range (``q_storage_dtype``) and widened to i32 in VMEM.
``dslot_matmul_pallas_batched`` is the batched entry point: it folds a
leading batch axis into M (every output tile stays inside one batch element
because ``M % block_m == 0``), which is exactly equivalent to a vmap but
keeps a single sequential grid, and forwards the prepared termination tables
and runtime precision of the unbatched entry.

Validated in interpret mode against ``ref.dslot_matmul_ref`` on CPU, and
compiled and run on a TPU v5e (``chip_smoke.py``; the Mosaic layout rules the
operands follow are in ``docs/kernel.md``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["dslot_matmul_pallas", "dslot_matmul_pallas_batched",
           "DslotMatmulOut", "colsum_tables", "select_block_k",
           "q_storage_dtype"]

_VMEM_BUDGET_BYTES = 12 * 1024 * 1024  # leave headroom below v5e's ~16 MiB
_LANE = 128                            # TPU lane width: K-chunk alignment
# The kernel's name in compiled programs and profiler traces (the HLO
# custom call is ``%dslot_matmul_pallas.N``): trace readers find it by this.
KERNEL_NAME = "dslot_matmul_pallas"


class DslotMatmulOut(NamedTuple):
    out: jax.Array               # (M, N) f32 — [relu](A_D @ W)
    planes_used: jax.Array       # (M/bm, N/bn) int32 — digit planes entered


def q_storage_dtype(n_bits: int, signed: bool = False) -> jnp.dtype:
    """Narrowest integer dtype holding the quantized-activation range.

    Unsigned ``n_bits``-bit quantization spans [0, 2^n_bits - 1] (u8 for the
    default 8-bit mode); signed spans ±(2^(n_bits-1) - 1) (i8 at 8 bits).
    This is the HBM footprint of the kernel's activation input — one byte
    per element at 8 bits versus the D int8 planes per element the
    materialized layout moved.  Values are widened to i32 in VMEM before
    digit extraction, so the storage dtype never changes results (pinned by
    ``tests/test_ktiling.py``); unsigned dtypes are exercised in interpret
    mode only — if Mosaic rejects u8 loads on real TPU, fall back to the
    next signed width here.
    """
    qmax = 2 ** (n_bits - 1) - 1 if signed else 2 ** n_bits - 1
    if signed:
        if qmax <= 127:
            return jnp.dtype(jnp.int8)
        if qmax <= 32767:
            return jnp.dtype(jnp.int16)
        return jnp.dtype(jnp.int32)
    if qmax <= 255:
        return jnp.dtype(jnp.uint8)
    if qmax <= 65535:
        return jnp.dtype(jnp.uint16)
    return jnp.dtype(jnp.int32)


def select_block_k(K: int, block_m: int, block_n: int, w_itemsize: int,
                   act_itemsize: int = 1,
                   budget: int = _VMEM_BUDGET_BYTES) -> int:
    """Largest K chunk whose working set fits the VMEM budget.

    Working set per grid step: one quantized-activation chunk
    (bm, bk) x ``act_itemsize`` (the integer ``q`` block digits are derived
    from — there is no separate plane chunk), one weight chunk (bk, bn), the
    f32 accumulator + output tile (bm, bn) and two f32 colsum rows (bn); the
    runtime precision, per-row budgets and termination flag are
    negligible.  Returns K itself when the whole reduction fits (the untiled
    fast path — which also makes the ``q`` chunk resident across all D
    planes); otherwise a lane-aligned chunk size.
    """
    fixed = 2 * block_m * block_n * 4 + 2 * block_n * 4
    per_k = block_m * act_itemsize + block_n * w_itemsize
    avail = budget - fixed
    if avail < per_k * _LANE:
        raise ValueError(
            f"block_m={block_m} x block_n={block_n} alone exceeds the VMEM "
            f"budget ({budget} B); shrink the output tile")
    bk = avail // per_k
    if bk >= K:
        return K
    return max(_LANE, (bk // _LANE) * _LANE)


def colsum_tables(w: jax.Array, block_k: int) -> tuple[jax.Array, jax.Array]:
    """|W| column-sum termination tables over the ``block_k``-chunked K axis.

    ``w``: (Kp, N) padded weights with ``Kp % block_k == 0``.  Returns
    ``(suffix_colsum (Kt, N), total_colsum (1, N))`` — per-chunk "what the
    current plane has not seen yet" suffixes and the all-of-K total that the
    kernel's remaining-contribution bound reads.  The ONE implementation
    shared by ``ops.dslot_prepare`` (weight-stationary: computed once) and
    the kernel's default path below (one-shot callers with no prepared
    tables).
    """
    Kp, N = w.shape
    assert Kp % block_k == 0, (Kp, block_k)
    absw = jnp.abs(w.astype(jnp.float32))
    chunk_colsum = absw.reshape(Kp // block_k, block_k, N).sum(axis=1)
    total_colsum = chunk_colsum.sum(axis=0, keepdims=True)       # (1, N)
    return total_colsum - jnp.cumsum(chunk_colsum, axis=0), total_colsum


def _kernel(npl_ref, bnd_ref, bud_ref, q_ref, w_ref, sfx_ref, tot_ref,
            out_ref, used_ref, acc_ref, term_ref, *, n_bits: int,
            n_planes: int, n_kchunks: int, relu: bool):
    d = pl.program_id(2)
    c = pl.program_id(3)

    j = pl.program_id(1)

    @pl.when(jnp.logical_and(d == 0, c == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        term_ref[0] = 0
        used_ref[0, j] = 0

    # Runtime precision: planes at d >= npl are skipped entirely (their MXU
    # pass AND their digit extraction are predicated off), so precision is a
    # per-call argument — changing it never retraces or re-lowers the kernel.
    # The static per-N-tile MSR bound (SMEM table indexed by j, baked at
    # dslot_prepare time from weight-side analysis — core.msr) caps the
    # plane count the same way: the effective plane budget of this tile is
    # min(n_planes_rt, row_budget, msr_bound[j]), so weight-inert tiles
    # never extract digits or issue MXU passes at all.
    npl = npl_ref[0, 0]
    terminated = jnp.logical_or(jnp.logical_or(term_ref[0] > 0, d >= npl),
                                d >= bnd_ref[0, j])

    @pl.when(jnp.logical_not(terminated))
    def _accumulate():
        # On-the-fly MSDF digit extraction (ref.sd_digit_plane, inlined):
        # plane d of the resident quantized chunk is bit (n_bits-1-d) of |q|
        # times sign(q) — derived here, never stored in HBM.
        q = q_ref[...].astype(jnp.int32)                   # (bm, bk)
        bit = (jnp.abs(q) >> (n_bits - 1 - d)) & 1
        digit = (bit * jnp.sign(q)).astype(jnp.float32)
        # Per-row precision: rows whose budget is exhausted contribute zero
        # digits from this plane on (the (block_m, 1) VMEM budget column of
        # this M-tile) — per-request precision inside a pooled batch.
        live = (bud_ref[...] > d).astype(jnp.float32)      # (bm, 1)
        plane = digit * live
        w = w_ref[...].astype(jnp.float32)                 # (bk, bn)
        scale = jnp.exp2(jnp.asarray(n_bits - 1, jnp.float32)
                         - d.astype(jnp.float32))
        acc_ref[...] += scale * jnp.dot(
            plane, w, preferred_element_type=jnp.float32)

        @pl.when(c == 0)
        def _count_plane():
            used_ref[0, j] += 1

        if relu:
            # Chunk-aware remaining-contribution bound (module docstring):
            # unseen chunks of this plane + all chunks of unseen planes up to
            # the runtime precision npl (geometric tail 2^(n_bits - npl)).
            tail = jnp.exp2(jnp.asarray(n_bits, jnp.float32)
                            - npl.astype(jnp.float32))
            rem = scale * sfx_ref[...] \
                + (scale - tail) * tot_ref[...]            # (1, bn)
            provably_neg = jnp.all(acc_ref[...] + rem < 0.0)
            term_ref[0] = jnp.where(provably_neg, 1, term_ref[0])

    @pl.when(jnp.logical_and(d == n_planes - 1, c == n_kchunks - 1))
    def _finalize():
        acc = acc_ref[...]
        if relu:
            acc = jnp.maximum(acc, 0.0)
            acc = jnp.where(term_ref[0] > 0, 0.0, acc)
        out_ref[...] = acc


def _check_tpu_blocks(block_m: int, block_n: int, block_k: int,
                      Kp: int) -> None:
    """Refuse, by name, a block the compiled kernel cannot lower.

    Mosaic needs the last two dims of every block to be multiples of
    (8, 128) or to span the array: ``block_m`` is the sublane dim of the
    activation, output and budget blocks, ``block_n`` the lane dim of the
    weight, output and colsum blocks, and a K chunk smaller than the padded
    K is the lane dim of the activation block.  Interpret mode has no such
    rule, so the CPU tests keep their small blocks.
    """
    bad = []
    if block_m % 8:
        bad.append(f"block_m={block_m} (needs a multiple of 8)")
    if block_n % _LANE:
        bad.append(f"block_n={block_n} (needs a multiple of {_LANE})")
    if block_k != Kp and block_k % _LANE:
        bad.append(f"block_k={block_k} (needs a multiple of {_LANE}, or "
                   f"the whole padded K={Kp})")
    if bad:
        raise ValueError("compiled TPU kernel cannot tile "
                         + ", ".join(bad) + "; interpret mode accepts any "
                         "block")


def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``m`` (shared with ops.py)."""
    r = (-x.shape[axis]) % m
    if r == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, r)
    return jnp.pad(x, pads)


@functools.partial(jax.jit, static_argnames=(
    "n_bits", "n_planes", "relu", "block_m", "block_n", "block_k",
    "interpret"))
def dslot_matmul_pallas(q: jax.Array, w: jax.Array, *, n_bits: int = 8,
                        n_planes: int | None = None, relu: bool = True,
                        block_m: int = 128, block_n: int = 128,
                        block_k: int | None = None,
                        n_planes_rt: jax.Array | None = None,
                        row_budget: jax.Array | None = None,
                        suffix_colsum: jax.Array | None = None,
                        total_colsum: jax.Array | None = None,
                        plane_bound: jax.Array | None = None,
                        interpret: bool | None = None) -> DslotMatmulOut:
    """Run the digit-serial matmul kernel with fused digit encoding.

    q:       (M, K) integer quantized activations, |q| < 2^n_bits (see
             ``ops.quantize_activations``); any int dtype — widened to i32
             inside the kernel.  Digit planes are derived from ``q`` in the
             kernel (``ref.sd_digit_plane``), never materialized.
    w:       (K, N) float32/bfloat16 weights.
    n_planes: STATIC plane-axis depth D of the grid (default ``n_bits``) —
             use for a statically-truncated precision where the grid itself
             shrinks (the fused one-shot path).
    block_k: K chunk size streamed through VMEM (None = auto-select the
             largest chunk that fits the budget; K is zero-padded to a
             multiple — zero rows contribute nothing to sums or bounds).
    n_planes_rt: optional RUNTIME precision (i32 scalar, <= D): planes at
             d >= n_planes_rt are predicated off — no retrace across
             precisions.  None runs all D planes.
    row_budget: optional RUNTIME per-row precision ((M,) i32): digits of row
             m beyond ``row_budget[m]`` are zeroed during extraction (a
             (block_m, 1) VMEM column per M-tile).  The scalar ``n_planes_rt``
             still bounds the whole call — pass the row max (as
             ``ops.dslot_execute`` does) so fully-exhausted planes skip
             their passes.  None means every row runs to ``n_planes_rt``.
    suffix_colsum / total_colsum: the |W| column-sum termination tables
             ((Kt, N) / (1, N) over the bk-padded K), precomputed once by
             ``ops.dslot_prepare`` for weight-stationary serving.  None
             recomputes them here via ``colsum_tables`` (the one-shot path).
    plane_bound: optional STATIC-per-weights plane upper bound per N-tile
             ((N/block_n,) i32, from ``DslotWeights.msr_bound``): tile j
             runs at most ``plane_bound[j]`` planes — weight-side sparsity
             baked at prepare time (``core.msr.tile_plane_bound`` emits
             only output-exact bounds).  Rides in SMEM like the runtime
             precision scalar; None means no weight-side cap.
    interpret: run the Pallas interpreter instead of compiling with Mosaic.
             None (default) interprets only off-TPU, so a TPU caller always
             gets the compiled kernel.
    M % block_m == 0 and N % block_n == 0 (callers pad — see ``ops.py``).
    Compiled, the blocks must also meet the TPU tiling rule
    (``_check_tpu_blocks``); interpret mode takes any block size.
    """
    M, K = q.shape
    K2, N = w.shape
    assert K == K2, (q.shape, w.shape)
    assert M % block_m == 0 and N % block_n == 0, (M, N, block_m, block_n)
    if n_planes is not None and n_planes < 1:
        raise ValueError(f"n_planes must be >= 1, got {n_planes}")
    D = min(n_planes or n_bits, n_bits)

    bk = block_k or select_block_k(K, block_m, block_n, w.dtype.itemsize,
                                   q.dtype.itemsize)
    vmem = (block_m * bk * q.dtype.itemsize) \
        + (bk * block_n * w.dtype.itemsize) \
        + 2 * (block_m * block_n * 4) + 2 * block_n * 4
    if vmem > _VMEM_BUDGET_BYTES:
        raise ValueError(
            f"working set {vmem / 2**20:.1f} MiB for block_k={bk} exceeds the "
            f"VMEM budget; pass a smaller block_k (or None to auto-select)")
    q = _pad_to(q, bk, axis=1)
    w = _pad_to(w, bk, axis=0)
    Kp = w.shape[0]
    Kt = Kp // bk
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret:
        _check_tpu_blocks(block_m, block_n, bk, Kp)

    if suffix_colsum is None or total_colsum is None:
        suffix_colsum, total_colsum = colsum_tables(w, bk)
    assert suffix_colsum.shape == (Kt, N), (suffix_colsum.shape, Kt, N)
    assert total_colsum.shape == (1, N), (total_colsum.shape, N)

    Mt, Nt = M // block_m, N // block_n
    if n_planes_rt is None:
        n_planes_rt = jnp.asarray(D, jnp.int32)
    npl = jnp.asarray(n_planes_rt, jnp.int32).reshape(1, 1)
    if plane_bound is None:
        bnd = jnp.full((1, Nt), D, jnp.int32)
    else:
        assert plane_bound.shape == (Nt,), (plane_bound.shape, N, block_n)
        bnd = jnp.asarray(plane_bound, jnp.int32).reshape(1, Nt)
    if row_budget is None:
        bud = jnp.full((M, 1), npl[0, 0], jnp.int32)
    else:
        assert row_budget.shape == (M,), (row_budget.shape, M)
        bud = jnp.asarray(row_budget, jnp.int32).reshape(M, 1)

    # Layouts Mosaic accepts: the last two block dims are (8, 128)-aligned
    # or span the array.  The plane-bound table is one whole SMEM block read
    # at [0, j]; planes_used is written one (1, Nt) SMEM row per M-tile (the
    # block stays resident across the j, d, c steps of that row), so SMEM
    # holds O(Nt) words at any M; the row budget is a (block_m, 1) VMEM
    # column; the suffix table gains a unit axis so its block is (1, bn).
    grid = (Mt, Nt, D, Kt)
    kernel = functools.partial(_kernel, n_bits=n_bits, n_planes=D,
                               n_kchunks=Kt, relu=relu)
    out, used = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j, d, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, Nt), lambda i, j, d, c: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((block_m, 1), lambda i, j, d, c: (i, 0)),
            pl.BlockSpec((block_m, bk), lambda i, j, d, c: (i, c)),
            pl.BlockSpec((bk, block_n), lambda i, j, d, c: (c, j)),
            pl.BlockSpec((None, 1, block_n), lambda i, j, d, c: (c, 0, j)),
            pl.BlockSpec((1, block_n), lambda i, j, d, c: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, block_n), lambda i, j, d, c: (i, j)),
            pl.BlockSpec((None, 1, Nt), lambda i, j, d, c: (i, 0, 0),
                         memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((M, N), jnp.float32),
            jax.ShapeDtypeStruct((Mt, 1, Nt), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),   # accumulator
            pltpu.SMEM((1,), jnp.int32),                   # termination flag
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(npl, bnd, bud, q, w, suffix_colsum.reshape(Kt, 1, N), total_colsum)
    return DslotMatmulOut(out=out, planes_used=used.reshape(Mt, Nt))


def dslot_matmul_pallas_batched(q: jax.Array, w: jax.Array, *,
                                n_bits: int = 8,
                                n_planes: int | None = None,
                                relu: bool = True,
                                block_m: int = 128, block_n: int = 128,
                                block_k: int | None = None,
                                n_planes_rt: jax.Array | None = None,
                                row_budget: jax.Array | None = None,
                                suffix_colsum: jax.Array | None = None,
                                total_colsum: jax.Array | None = None,
                                plane_bound: jax.Array | None = None,
                                interpret: bool | None = None
                                ) -> DslotMatmulOut:
    """Batched entry point: q (B, M, K) sharing one weight matrix.

    The batch axis is folded into M — with ``M % block_m == 0`` every output
    tile lies inside a single batch element, so results and per-tile
    termination are identical to B independent kernel launches, but the grid
    stays one sequential sweep.  The full unbatched surface passes through:
    ``n_planes_rt`` (runtime scalar precision), ``row_budget`` ((B,)
    per-request or (B, M) per-row budgets, expanded to the folded rows), the
    prepared ``suffix_colsum``/``total_colsum`` termination tables, and the
    static per-N-tile ``plane_bound`` (weight-side, batch-invariant) — so
    batched serving callers reuse ``dslot_prepare``'s tables instead of
    recomputing |W| column-sums per call.  Returns out (B, M, N) and
    planes_used (B, M/bm, N/bn).
    """
    B, M, K = q.shape
    assert M % block_m == 0, (M, block_m)
    if row_budget is not None:
        row_budget = jnp.asarray(row_budget, jnp.int32)
        if row_budget.shape == (B,):            # one budget per batch element
            row_budget = jnp.repeat(row_budget, M)
        else:
            assert row_budget.shape == (B, M), (row_budget.shape, B, M)
            row_budget = row_budget.reshape(B * M)
    r = dslot_matmul_pallas(q.reshape(B * M, K), w, n_bits=n_bits,
                            n_planes=n_planes, relu=relu,
                            block_m=block_m, block_n=block_n,
                            block_k=block_k, n_planes_rt=n_planes_rt,
                            row_budget=row_budget,
                            suffix_colsum=suffix_colsum,
                            total_colsum=total_colsum,
                            plane_bound=plane_bound, interpret=interpret)
    N = r.out.shape[-1]
    return DslotMatmulOut(
        out=r.out.reshape(B, M, N),
        planes_used=r.planes_used.reshape(B, M // block_m, -1))
