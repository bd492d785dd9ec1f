"""Digit-plane DSLOT kernel benchmark: skipped-MXU-pass fraction vs output
negativity (the TPU adaptation of Fig. 9), runtime-precision scaling,
``block_k`` streaming sweep, and per-layer planes-skipped for the MNIST
network through the unified layer API — the software proxy for the paper's
energy-saving claim.  Wall-times are for the jnp path (CPU container; Pallas
numbers are structural — interpret mode is not a performance proxy).

``--sweep-precision`` measures the prepare/execute split: calls/s of
``dslot_execute`` against cached weight tables vs the fused per-call
``dslot_matmul`` (which re-sorts/re-encodes the weight side every call),
plus skipped-frac per runtime precision — written to ``BENCH_precision.json``.

``--compare-encoding`` measures fused in-kernel digit encoding against the
pre-fusion materialized (D, M, K) plane-tensor path (kept verbatim in this
file as the baseline): wall-clock, XLA bytes-moved via
``jax.jit(...).lower().compile().cost_analysis()``, the activation-stream
footprint, and a bit-exactness cross-check — written to
``BENCH_kernel.json``.  Exits nonzero (CI-fatal) if the fused path moves
more activation bytes than the materialized one.

``--msr-profile`` profiles weight-side digit sparsity on the MNIST CNN:
per-layer MSR (Most-Significant-Run) histograms of the quantized weights,
the measured planes-ISSUED reduction from the static per-N-tile MSR bound
(``dslot_prepare(msr_bound=True)``) on a channel-pruned variant with full
forward bit-exactness against the unbounded path, and the CSD/Booth
nonzero-digit enumeration prototype (``core.csd``) head-to-head against
the dense-plane scan's digit-slot count.  Results MERGE into the same
``BENCH_kernel.json`` under ``"msr_profile"``; exits nonzero if outputs
diverge, the bound saves nothing, or CSD is not sparser than binary.

Standalone CLI (used by the CI smoke job):
    python benchmarks/bench_kernel.py [--smoke] [--json out.json]
        [--sweep-precision [--precision-json BENCH_precision.json]]
        [--compare-encoding [--kernel-json BENCH_kernel.json]]
        [--msr-profile [--kernel-json BENCH_kernel.json]]
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.kernels.ops import dslot_matmul


def _timeit(fn, *args, iters=3, **kw):
    fn(*args, **kw)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
        jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def run(smoke: bool = False) -> list[str]:
    rows = []
    rng = np.random.default_rng(0)
    M = K = N = 64 if smoke else 256
    x = jnp.asarray(np.maximum(rng.normal(0.3, 0.4, (M, K)), 0), jnp.float32)
    bm = bn = 32 if smoke else 64

    for dead_frac in (0.0, 0.25, 0.5, 0.75):
        w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
        n_dead = int(N * dead_frac)
        if n_dead:
            w[:, rng.permutation(N)[:n_dead]] -= 0.10
        out, st = dslot_matmul(x, jnp.asarray(w), backend="jnp",
                               sort_columns=True, block_m=bm, block_n=bn)
        rows.append(f"kernel.skipped_frac_dead{int(dead_frac*100)},"
                    f"{float(st.skipped_frac):.4f},sorted-tiles")

    # block_k streaming sweep: same workload, weights streamed through VMEM
    # in chunks.  The chunk-aware bound can only terminate earlier, so the
    # skipped fraction is monotone non-decreasing as chunks shrink.
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    w[:, rng.permutation(N)[:N // 2]] -= 0.10
    for bk in (None, K, K // 2, K // 4):
        out, st = dslot_matmul(x, jnp.asarray(w), backend="jnp",
                               sort_columns=True, block_m=bm, block_n=bn,
                               block_k=bk)
        us = _timeit(dslot_matmul, x, jnp.asarray(w), backend="jnp",
                     sort_columns=True, block_m=bm, block_n=bn, block_k=bk)
        tag = "auto" if bk is None else str(bk)
        rows.append(f"kernel.blockk{tag}_skipped_frac,"
                    f"{float(st.skipped_frac):.4f},us={us:.0f}")

    w = jnp.asarray(rng.normal(0, 0.05, (K, N)), jnp.float32)
    for D in (8, 6, 4, 2):
        us = _timeit(dslot_matmul, x, w, backend="jnp", n_planes=D,
                     block_m=bm, block_n=bn)
        out, _ = dslot_matmul(x, w, backend="jnp", n_planes=D,
                              block_m=bm, block_n=bn)
        ref = jnp.maximum(x @ w, 0)
        rel = float(jnp.abs(out - ref).mean() / (jnp.abs(ref).mean() + 1e-9))
        rows.append(f"kernel.planes{D}_us,{us:.0f},rel_err={rel:.4f}")

    # per-layer planes-skipped for the MNIST network through the layer API
    # (trained-free: random weights biased negative in the head so early
    # termination has something to kill — the per-layer reporting path is
    # what's exercised here, not the paper's accuracies).
    from repro.configs.dslot_mnist import CONFIG
    from repro.core.mnist_cnn import forward_dslot, init_cnn
    params = init_cnn(CONFIG, jax.random.PRNGKey(0))
    imgs = jnp.asarray(rng.uniform(0, 1, (4 if smoke else 16, 28, 28)),
                       jnp.float32)
    res = forward_dslot(params, imgs, CONFIG, block_m=32,
                        block_k=None if smoke else 64)
    for name, st in res.layer_stats.items():
        used = np.asarray(st.planes_used)
        rows.append(f"kernel.layer_{name}_planes_used,"
                    f"{used.mean():.3f},skipped={float(st.skipped_frac):.4f}")

    # pallas parity check at bench scale, tiled K (the kernel consumes
    # quantized activations and encodes digits in-kernel; the oracle
    # evaluates over an explicitly materialized plane tensor).  Interpreted
    # off-TPU at small blocks; compiled on a TPU at the blocks Mosaic takes.
    from repro.kernels.ref import make_planes, dslot_matmul_ref
    from repro.kernels.dslot_matmul import dslot_matmul_pallas
    tpu = jax.default_backend() == "tpu"
    n, blk = (256, 128) if tpu else (64, 32)
    aq = jnp.asarray(rng.integers(0, 256, (n, n)), jnp.int32)
    wp = jnp.asarray(rng.normal(0, 0.05, (n, n)), jnp.float32)
    o1 = dslot_matmul_pallas(aq, wp, block_m=blk, block_n=blk,
                             block_k=blk).out
    o2 = dslot_matmul_ref(make_planes(aq, 8), wp, 8)
    rows.append(f"kernel.pallas_vs_ref_maxerr,"
                f"{float(jnp.abs(o1 - o2).max()):.2e},"
                f"{'compiled' if tpu else 'interpret'}-tiled-k")
    return rows


# --------------------------------------------------- encoding comparison

def _materialized_execute(prep, x, npl):
    """The PRE-FUSION execution path, kept verbatim as the benchmark
    baseline: encode ALL digit planes of the quantized activations into a
    (D, M, K) int8 tensor, restack it into per-step chunks, then stream the
    planes through the same scaled-matmul scan with the same chunk-aware
    termination replay.  This is what ``dslot_execute`` did before digit
    encoding was fused into the kernels — byte-for-byte the old dataflow,
    so the fused path can be gated on (a) moving strictly fewer bytes and
    (b) bit-exact outputs/planes_used against it.
    """
    from repro.kernels.ref import make_planes

    cfg = prep
    M, K = x.shape
    q, step = ops.quantize_activations(x, n_bits=cfg.n_bits,
                                       signed=cfg.signed, scale=cfg.x_scale)
    planes = make_planes(q, cfg.n_bits)                     # (D, M, K) HBM
    D = planes.shape[0]
    npl_c = jnp.clip(jnp.asarray(npl, jnp.int32), 1, D)
    pmask = (jnp.arange(D) < npl_c)[:, None, None]
    planes = planes * pmask.astype(planes.dtype)
    planes = jnp.pad(planes, [(0, 0), (0, (-M) % cfg.block_m),
                              (0, cfg.w.shape[0] - K)])
    D, Mp, Kp = planes.shape
    N = cfg.w.shape[1]
    bk = cfg.block_k
    Kt = Kp // bk
    Mt, Nt = Mp // cfg.block_m, N // cfg.block_n
    w_chunks = cfg.w.astype(jnp.float32).reshape(Kt, bk, N)
    # the old layout: every plane of every chunk, stacked — D*M*K int8
    p_chunks = planes.reshape(D, Mp, Kt, bk).transpose(0, 2, 1, 3) \
        .reshape(D * Kt, Mp, bk)
    scales = jnp.exp2(jnp.asarray(cfg.n_bits - 1, jnp.float32)
                      - jnp.arange(D, dtype=jnp.float32))
    tail = jnp.exp2(jnp.asarray(cfg.n_bits, jnp.float32)
                    - npl_c.astype(jnp.float32))
    step_rem = (scales[:, None, None] * cfg.suffix_colsum[None]
                + ((scales - tail)[:, None, None]
                   * cfg.total_colsum[0][None, None, :])).reshape(D * Kt, N)

    def body(acc, s):
        p, c, scale, rem = s
        wc = jax.lax.dynamic_index_in_dim(w_chunks, c, keepdims=False)
        acc = acc + scale * jnp.dot(p.astype(jnp.float32), wc,
                                    preferred_element_type=jnp.float32)
        dead = jnp.all((acc + rem[None, :]).reshape(
            Mt, cfg.block_m, Nt, cfg.block_n) < 0.0, axis=(1, 3))
        return acc, dead

    c_idx = jnp.tile(jnp.arange(Kt), D)
    acc, dead_after = jax.lax.scan(
        body, jnp.zeros((Mp, N), jnp.float32),
        (p_chunks, c_idx, jnp.repeat(scales, Kt), step_rem))
    out = jnp.maximum(acc, 0.0)
    ever = jnp.any(dead_after, axis=0)
    first = jnp.argmax(dead_after, axis=0)
    used = jnp.where(ever, first // Kt + 1, D).astype(jnp.int32)
    used = jnp.minimum(used, npl_c)
    return out[:M, :cfg.d_out] * step, used


def _bytes_accessed(fn, *args) -> float:
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    if not isinstance(cost, dict):                  # some versions: [dict]
        cost = cost[0]
    return float(cost.get("bytes accessed", float("nan")))


def _max_int_tensor_bytes(fn, *args) -> int:
    """Largest integer-typed tensor anywhere in ``fn``'s jaxpr, in bytes.

    The structural detector for a reintroduced digit-plane materialization:
    the old path's (D, M, K) plane tensor (or its (D*Kt, M, bk) restack) is
    by far the largest integer intermediate either path could create, so
    'fused max int tensor < plane-tensor bytes' proves no plane-sized
    activation encoding exists in the traced graph — independent of
    whatever XLA's cost model reports.
    """
    import re

    txt = str(jax.make_jaxpr(fn)(*args))            # includes scan bodies
    best = 0
    for m in re.finditer(r"\b[iu](\d+)\[([\d,]+)\]", txt):
        elems = 1
        for d in m.group(2).split(","):
            elems *= int(d)
        best = max(best, elems * int(m.group(1)) // 8)
    return best


def run_encoding_comparison(smoke: bool = False) -> dict:
    """Fused in-kernel digit encoding vs the materialized (D, M, K) plane
    tensor: wall-clock, XLA bytes-moved (``cost_analysis``), the
    activation-stream footprint each path hands to its compute, and a
    bit-exactness cross-check.  Emits the ``BENCH_kernel.json`` payload;
    byte regressions (fused moving MORE than materialized, or a <4x
    activation-stream reduction) are recorded in ``report["violations"]``
    and turned into a nonzero exit by the CLI AFTER the artifact is
    written; diverging outputs/planes_used raise immediately.
    """
    from repro.kernels.dslot_matmul import q_storage_dtype

    rng = np.random.default_rng(0)
    M = K = N = 64 if smoke else 256
    bm = bn = 32 if smoke else 64
    bk = K // 2
    n_bits = 8
    x = jnp.asarray(np.maximum(rng.normal(0.3, 0.4, (M, K)), 0), jnp.float32)
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    w[:, rng.permutation(N)[:N // 2]] -= 0.10
    prep = ops.dslot_prepare(jnp.asarray(w), n_bits=n_bits, relu=True,
                             block_m=bm, block_n=bn, block_k=bk,
                             backend="jnp")
    prep = prep.with_scale(ops.calibrate_scale(x))
    iters = 3 if smoke else 10

    def fused(prep, x, npl):
        return ops._execute_core(prep, x, npl)

    fused_jit = jax.jit(fused)
    mat_jit = jax.jit(_materialized_execute)
    report = {"smoke": smoke, "shape": [M, K, N], "block": [bm, bn, bk],
              "n_bits": n_bits, "sweep": [], "violations": []}
    D = n_bits
    q_itemsize = q_storage_dtype(n_bits, prep.signed).itemsize
    Kp = prep.w.shape[0]
    # bytes moved are a property of the lowered graph, not of the traced
    # runtime precision — measure each path once, outside the sweep
    npl0 = jnp.asarray(n_bits, jnp.int32)
    fused_bytes = _bytes_accessed(fused, prep, x, npl0)
    mat_bytes = _bytes_accessed(_materialized_execute, prep, x, npl0)
    bytes_known = not (np.isnan(fused_bytes) or np.isnan(mat_bytes))
    # structural gate on the REAL traced graphs: the fused path must not
    # contain any plane-tensor-sized integer intermediate (and the detector
    # is validated against the materialized path, which must contain one)
    plane_bytes = D * ((M + bm - 1) // bm * bm) * Kp
    fused_int_max = _max_int_tensor_bytes(fused, prep, x, npl0)
    mat_int_max = _max_int_tensor_bytes(_materialized_execute, prep, x, npl0)
    assert mat_int_max >= plane_bytes, \
        (mat_int_max, plane_bytes, "detector failed to see the plane tensor")
    report["plane_tensor_bytes"] = plane_bytes
    report["max_int_tensor_bytes"] = {"fused": fused_int_max,
                                      "materialized": mat_int_max}
    if fused_int_max >= plane_bytes:
        report["violations"].append(
            f"fused graph contains a plane-tensor-sized integer "
            f"intermediate ({fused_int_max} >= {plane_bytes} B): digit "
            f"encoding is being materialized again")
    # the activation-stream model (what each path hands its kernel/scan):
    # analytic by construction; the structural gate above checks the graph
    act_fused = M * Kp * q_itemsize
    act_mat = D * M * Kp * 1
    if act_mat / act_fused < 4.0:
        report["violations"].append(
            f"activation-stream reduction {act_mat / act_fused:.1f}x "
            f"< 4x at n_bits={n_bits}")
    for npl_i in (8, 4, 2):
        npl = jnp.asarray(npl_i, jnp.int32)
        of, sf = fused_jit(prep, x, npl)
        om, um = mat_jit(prep, x, npl)
        np.testing.assert_array_equal(np.asarray(of), np.asarray(om),
                                      err_msg=f"n_planes={npl_i}")
        np.testing.assert_array_equal(np.asarray(sf.planes_used),
                                      np.asarray(um),
                                      err_msg=f"n_planes={npl_i}")
        fused_us = _timeit(fused_jit, prep, x, npl, iters=iters)
        mat_us = _timeit(mat_jit, prep, x, npl, iters=iters)
        report["sweep"].append({
            "n_planes": npl_i,
            "wall_us": {"fused": fused_us, "materialized": mat_us},
            "bit_exact": True,
        })
    # the activation tensor each path streams through its compute: the
    # fused kernels read the quantized block itself; the old path wrote
    # and re-read every digit plane of it
    report["activation_stream_bytes"] = {
        "fused": act_fused, "materialized": act_mat,
        "reduction": act_mat / act_fused}
    report["bytes_accessed"] = {
        "fused": fused_bytes, "materialized": mat_bytes,
        "known": bytes_known,
        "reduction": mat_bytes / fused_bytes if bytes_known else None}
    if bytes_known and fused_bytes > mat_bytes:
        report["violations"].append(
            f"fused path moves MORE bytes than materialized: "
            f"{fused_bytes} > {mat_bytes}")
    return report


# --------------------------------------------------- weight-side sparsity

def run_msr_profile(smoke: bool = False) -> dict:
    """Weight-side digit sparsity on the paper's MNIST CNN.

    Three measurements, one artifact block:

    * **MSR histograms** — per-layer Most-Significant-Run depth of the
      int8-quantized weights (``core.msr.msr_histogram``), the trained-net
      statistic the static plane bound exploits.
    * **Static MSR bound, measured** — the network's conv layer is
      structurally pruned (the weakest half of its output channels zeroed
      — the standard dead-neuron deployment transform) and prepared with
      ``sort_columns=True`` so the zero columns cluster into whole N-tiles;
      the same prepared state runs with and without ``msr_bound`` and the
      report carries Σ planes-issued (and MXU passes) for both, gated on
      (a) bit-identical logits and (b) a strictly positive reduction.
      The unpruned network is profiled alongside for honesty: dense random
      weights have no output-inert tile, so its reduction is 0 — the bound
      is a *sparsity* win, not a free lunch.
    * **CSD head-to-head** — the activations' CSD/Booth recoding
      (``core.csd``) vs plain binary vs the dense plane scan: essential
      (nonzero) digit count per path, with ``csd_matmul`` asserted
      bit-equal to the integer product ``q @ w_q``.
    """
    import dataclasses

    from repro.configs.dslot_mnist import CONFIG
    from repro.core.conv import im2col
    from repro.core.csd import (binary_digit_count, csd_matmul, csd_recode,
                                essential_digit_count)
    from repro.core.mnist_cnn import _pool_flatten, init_cnn
    from repro.core.msr import msr_histogram, quantize_weights
    from repro.layers import DslotConv2d, DslotDense

    rng = np.random.default_rng(0)
    cfg = CONFIG
    m, k = cfg.conv_channels, cfg.kernel_size
    side = (cfg.image_size - k + 1) // cfg.pool
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    imgs = jnp.asarray(rng.uniform(0, 1, (4 if smoke else 16, 28, 28)),
                       jnp.float32)

    # conv weights as the (k*k, M) im2col matrix the kernel actually sees
    conv_mat = np.asarray(jnp.transpose(params.conv, (1, 2, 0))
                          .reshape(k * k, m))
    dense_mat = np.asarray(params.dense)

    # structured pruning: zero the weakest half of the conv output channels
    l2 = np.linalg.norm(conv_mat, axis=0)
    pruned_ch = np.argsort(l2)[:m // 2]
    conv_pruned = conv_mat.copy()
    conv_pruned[:, pruned_ch] = 0.0

    report = {"smoke": smoke, "n_bits": cfg.n_bits,
              "pruned_channels": sorted(int(c) for c in pruned_ch),
              "violations": [],
              "msr_histograms": {
                  "conv1": msr_histogram(jnp.asarray(conv_mat), cfg.n_bits),
                  "conv1_pruned": msr_histogram(jnp.asarray(conv_pruned),
                                                cfg.n_bits),
                  "dense1": msr_histogram(jnp.asarray(dense_mat),
                                          cfg.n_bits)}}

    def _forward(conv_w, *, msr_bound):
        """Full-network forward through the layer API; returns logits and
        Σ planes-issued / Σ MXU passes / Σ planes-bounded per layer."""
        conv = DslotConv2d(in_channels=1, out_channels=m, kernel_size=k,
                           name="conv1", n_bits=cfg.n_bits, relu=True,
                           sort_columns=True, block_m=32, block_n=2)
        head = DslotDense(d_in=m * side * side, d_out=cfg.n_classes,
                          name="dense1", n_bits=cfg.n_bits, relu=False,
                          signed=False, block_m=32, block_n=2)
        wc = jnp.asarray(conv_w).reshape(k, k, 1, m)
        cp = conv.prepare({"w": wc})
        hp = head.prepare({"w": jnp.asarray(dense_mat)})
        if not msr_bound:
            cp = {**cp, "dslot": dataclasses.replace(cp["dslot"],
                                                     msr_bound=None)}
            hp = {**hp, "dslot": dataclasses.replace(hp["dslot"],
                                                     msr_bound=None)}
        x, conv_st = conv.apply(cp, imgs[..., None])
        logits, head_st = head.apply(hp, _pool_flatten(x, cfg))
        layers = {}
        for name, st, prep in (("conv1", conv_st, cp["dslot"]),
                               ("dense1", head_st, hp["dslot"])):
            Kt = prep.w.shape[0] // prep.block_k
            issued = int(np.asarray(st.planes_used).sum())
            layers[name] = {
                "planes_issued": issued,
                "mxu_passes": issued * Kt,
                "planes_bounded": (0 if st.planes_bounded is None else
                                   int(np.asarray(st.planes_bounded).sum())),
                "bound_table": (None if prep.msr_bound is None else
                                np.asarray(prep.msr_bound).tolist()),
            }
        return np.asarray(logits), layers

    for tag, conv_w in (("pruned", conv_pruned), ("unpruned", conv_mat)):
        yb, lb = _forward(conv_w, msr_bound=True)
        yu, lu = _forward(conv_w, msr_bound=False)
        np.testing.assert_array_equal(
            yb, yu, err_msg=f"MSR bound changed {tag} logits")
        issued_b = sum(d["planes_issued"] for d in lb.values())
        issued_u = sum(d["planes_issued"] for d in lu.values())
        passes_b = sum(d["mxu_passes"] for d in lb.values())
        passes_u = sum(d["mxu_passes"] for d in lu.values())
        report[tag] = {
            "bit_exact": True,
            "layers": {n: {"bounded": lb[n], "unbounded": lu[n]}
                       for n in lb},
            "planes_issued": {"bounded": issued_b, "unbounded": issued_u,
                              "reduction": 1.0 - issued_b / issued_u},
            "mxu_passes": {"bounded": passes_b, "unbounded": passes_u,
                           "reduction": 1.0 - passes_b / passes_u},
        }
    if report["pruned"]["planes_issued"]["reduction"] <= 0.0:
        report["violations"].append(
            "MSR bound saved no issued planes on the pruned CNN "
            f"({report['pruned']['planes_issued']})")

    # CSD/Booth nonzero-digit enumeration vs the dense-plane scan, on the
    # conv layer's real activation stream (im2col'd images, quantized)
    cols = im2col(imgs[..., None], k, 1, "valid").reshape(-1, k * k)
    q, _ = ops.quantize_activations(cols, n_bits=cfg.n_bits, signed=False)
    q = q[:64 if smoke else 512]
    w_q = quantize_weights(jnp.asarray(conv_mat), cfg.n_bits)
    out_csd, nz_planes = csd_matmul(q, w_q, cfg.n_bits)
    np.testing.assert_array_equal(
        np.asarray(out_csd), np.asarray(q) @ np.asarray(w_q),
        err_msg="CSD matmul diverged from the integer product")
    essential = int(essential_digit_count(csd_recode(q, cfg.n_bits)))
    binary = int(binary_digit_count(q, cfg.n_bits))
    dense_slots = cfg.n_bits * int(q.size)
    report["csd"] = {
        "bit_exact": True,
        "activation_rows": int(q.shape[0]),
        "essential_digits_csd": essential,
        "nonzero_digits_binary": binary,
        "dense_plane_digit_slots": dense_slots,
        "nonzero_planes": int(nz_planes),
        "csd_vs_dense_reduction": 1.0 - essential / dense_slots,
        "csd_vs_binary_reduction": 1.0 - essential / max(binary, 1),
    }
    if essential > binary:
        report["violations"].append(
            f"CSD recoding is denser than binary ({essential} > {binary})")
    return report


def run_precision_sweep(smoke: bool = False) -> dict:
    """Prepare-once/execute-many amortization + skipped-frac per precision.

    Two costs are measured per precision D:

    * ``first_call_us`` — latency of the FIRST call at a new precision.
      The fused path takes D as a static argument, so every precision is a
      fresh trace + compile; ``dslot_execute`` takes it as a runtime value
      against cached weight tables, so switching precision costs one normal
      dispatch.  This is the serving-path win: precision becomes a
      per-request parameter instead of a recompile.
    * ``steady_us`` — steady-state per-call latency (jnp backend on CPU;
      note the split path always scans ``n_bits`` plane chunks with masked
      digits — on TPU the Pallas kernel predicates those passes off).
    """
    rng = np.random.default_rng(0)
    M = K = N = 64 if smoke else 256
    bm = bn = 32 if smoke else 64
    bk = K // 4
    x = jnp.asarray(np.maximum(rng.normal(0.3, 0.4, (M, K)), 0), jnp.float32)
    w = rng.normal(0, 0.05, (K, N)).astype(np.float32)
    w[:, rng.permutation(N)[:N // 2]] -= 0.10          # dead columns
    w = jnp.asarray(w)
    iters = 3 if smoke else 10

    # fused baseline: first call per precision = fresh trace + compile
    fused_first, fused_steady = {}, {}
    for D in (8, 6, 4, 2):
        t0 = time.perf_counter()
        dslot_matmul(x, w, backend="jnp", n_planes=D, sort_columns=True,
                     block_m=bm, block_n=bn, block_k=bk)[0] \
            .block_until_ready()
        fused_first[D] = (time.perf_counter() - t0) * 1e6
        fused_steady[D] = _timeit(
            dslot_matmul, x, w, backend="jnp", n_planes=D,
            sort_columns=True, block_m=bm, block_n=bn, block_k=bk,
            iters=iters)

    n0 = ops.prepare_call_count()
    t0 = time.perf_counter()
    prep = ops.dslot_prepare(w, relu=True, sort_columns=True, block_m=bm,
                             block_n=bn, block_k=bk, backend="jnp")
    prep = prep.with_scale(ops.calibrate_scale(x))
    prepare_us = (time.perf_counter() - t0) * 1e6
    prepares = ops.prepare_call_count() - n0

    ops.dslot_execute(prep, x, n_planes=8)[0].block_until_ready()  # warm
    n1 = ops.prepare_call_count()
    sweep = []
    for D in (8, 6, 4, 2):
        t0 = time.perf_counter()
        out, st = ops.dslot_execute(prep, x, n_planes=D)
        out.block_until_ready()
        ex_first = (time.perf_counter() - t0) * 1e6
        ex_us = _timeit(ops.dslot_execute, prep, x, n_planes=D, iters=iters)
        ref = jnp.maximum(x @ w, 0)
        rel = float(jnp.abs(out - ref).mean()
                    / (jnp.abs(ref).mean() + 1e-9))
        sweep.append({
            "n_planes": D,
            "first_call_us": {"fused": fused_first[D], "execute": ex_first},
            "precision_switch_speedup": fused_first[D] / ex_first,
            "steady_us": {"fused": fused_steady[D], "execute": ex_us},
            "execute_calls_per_s": 1e6 / ex_us,
            "skipped_frac": float(st.skipped_frac),
            "planes_used_mean": float(jnp.mean(
                st.planes_used.astype(jnp.float32))),
            "rel_err_vs_float": rel,
        })
    assert ops.prepare_call_count() == n1, \
        "execute sweep must not re-prepare weights"
    return {"smoke": smoke, "shape": [M, K, N], "block": [bm, bn, bk],
            "prepares": prepares, "prepare_us": prepare_us, "sweep": sweep}


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI smoke job)")
    ap.add_argument("--json", type=str, default=None,
                    help="also write rows as JSON to this path")
    ap.add_argument("--sweep-precision", action="store_true",
                    help="measure prepare-once/execute-many amortization "
                         "and skipped-frac per runtime precision")
    ap.add_argument("--precision-json", type=str,
                    default="BENCH_precision.json",
                    help="output path for the --sweep-precision report")
    ap.add_argument("--compare-encoding", action="store_true",
                    help="fused in-kernel digit encoding vs the "
                         "materialized (D, M, K) plane-tensor baseline "
                         "(wall-clock, bytes moved, bit-exactness)")
    ap.add_argument("--kernel-json", type=str, default="BENCH_kernel.json",
                    help="output path for the --compare-encoding and "
                         "--msr-profile reports (merged, not clobbered)")
    ap.add_argument("--msr-profile", action="store_true",
                    help="weight-side digit sparsity: per-layer MSR "
                         "histograms, static-bound planes-issued reduction "
                         "on the MNIST CNN (bit-exact gated), and the "
                         "CSD/Booth vs dense-plane digit count")
    args = ap.parse_args()
    if args.msr_profile:
        import os
        report = run_msr_profile(smoke=args.smoke)
        for tag in ("pruned", "unpruned"):
            pi = report[tag]["planes_issued"]
            print(f"{tag}: planes issued {pi['bounded']} bounded vs "
                  f"{pi['unbounded']} unbounded "
                  f"({pi['reduction']:.1%} reduction, bit-exact)")
        c = report["csd"]
        print(f"csd: {c['essential_digits_csd']} essential digits vs "
              f"{c['nonzero_digits_binary']} binary nonzeros vs "
              f"{c['dense_plane_digit_slots']} dense plane slots "
              f"({c['csd_vs_dense_reduction']:.1%} vs dense)")
        # merge into the shared kernel artifact: --compare-encoding runs
        # earlier in the CI job and owns the top-level keys
        merged = {}
        if os.path.exists(args.kernel_json):
            with open(args.kernel_json) as f:
                merged = json.load(f)
        merged["msr_profile"] = report
        with open(args.kernel_json, "w") as f:
            json.dump(merged, f, indent=2)
        print(f"merged msr_profile into {args.kernel_json}")
        if report["violations"]:
            raise SystemExit("; ".join(report["violations"]))
        return
    if args.compare_encoding:
        report = run_encoding_comparison(smoke=args.smoke)
        print("n_planes,fused_us,materialized_us")
        for row in report["sweep"]:
            print(f"{row['n_planes']},{row['wall_us']['fused']:.0f},"
                  f"{row['wall_us']['materialized']:.0f}")
        a = report["activation_stream_bytes"]
        print(f"activation stream: fused={a['fused']} B "
              f"materialized={a['materialized']} B ({a['reduction']:.1f}x)")
        i = report["max_int_tensor_bytes"]
        print(f"largest int tensor in graph: fused={i['fused']} B "
              f"materialized={i['materialized']} B "
              f"(plane tensor = {report['plane_tensor_bytes']} B)")
        b = report["bytes_accessed"]
        print(f"bytes accessed (XLA): fused={b['fused']:.0f} "
              f"materialized={b['materialized']:.0f}"
              + (f" ({b['reduction']:.2f}x)" if b["known"] else
                 " (cost_analysis unavailable: gate skipped)"))
        # write the artifact BEFORE gating so a red CI still uploads the
        # numbers that explain the regression
        with open(args.kernel_json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.kernel_json}")
        if report["violations"]:
            raise SystemExit("; ".join(report["violations"]))
        return
    if args.sweep_precision:
        report = run_precision_sweep(smoke=args.smoke)
        print("n_planes,switch_us_fused,switch_us_execute,switch_speedup,"
              "steady_us_execute,skipped_frac")
        for row in report["sweep"]:
            print(f"{row['n_planes']},{row['first_call_us']['fused']:.0f},"
                  f"{row['first_call_us']['execute']:.0f},"
                  f"{row['precision_switch_speedup']:.1f},"
                  f"{row['steady_us']['execute']:.0f},"
                  f"{row['skipped_frac']:.4f}")
        with open(args.precision_json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.precision_json}")
        return
    rows = run(smoke=args.smoke)
    print("name,value,derived")
    for row in rows:
        print(row, flush=True)
    if args.json:
        records = []
        for row in rows:
            name, value, derived = row.split(",", 2)
            records.append({"name": name, "value": value, "derived": derived})
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "rows": records}, f, indent=2)


if __name__ == "__main__":
    main()
