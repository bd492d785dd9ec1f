"""Unified DSLOT layer API: ``DslotDense`` and ``DslotConv2d``.

Every model-facing use of the digit-plane engine goes through these two
layers, now built on the **prepare/execute split** (``kernels.ops``):

* ``init`` returns params WITH prepared state — the weight lowering
  (column sort, padding, block geometry, termination tables) runs exactly
  once per layer per model lifetime;
* ``prepare(params)`` attaches/refreshes the prepared state for externally
  trained weights;
* ``calibrate(params, x_sample)`` stores a fixed activation-quantization
  scale in the prepared state, removing the data-dependent ``jnp.max`` from
  the per-request hot path;
* ``apply(params, x, n_planes=...)`` executes at a RUNTIME precision — an
  explicit argument, a value from the active ``repro.runtime``
  precision scope (policy-supplied, possibly a per-row jax array), or the
  layer's static default, in that order.  Changing precision never
  re-prepares weights and never retraces.  Per-row budgets are consumed
  INSIDE the kernel (a per-row budget column) and digit planes are derived
  in-kernel from the quantized activations — no plane tensor, no
  row-masking pass outside the kernel (see ``kernels/ops.py``).

Per-call statistics (``planes_used``, ``skipped_frac``, per-row effective
planes, weight-side ``planes_bounded``) surface both as return values and
through the ``repro.models.stats`` side channel (keys
``{name}.skipped_frac`` / ``{name}.planes_used_mean`` /
``{name}.row_planes_used`` / ``{name}.planes_bounded_mean``), so serving
and benchmark entry points can report the paper's energy-saving proxy per
layer and per request.

``DslotConv2d`` lowers convolution through ``core.conv.im2col`` (valid or
same padding) so conv SOPs hit exactly the same kernel datapath as dense
layers — the DSLR-CNN extension of the paper's PE array, at tile
granularity.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.conv import im2col
from repro.kernels.ops import (DslotStats, DslotWeights, calibrate_scale,
                               dslot_execute, dslot_prepare)
from repro.models import stats as stats_channel
from repro.runtime import current_precision

__all__ = ["DslotDense", "DslotConv2d", "DslotLayerStats"]


class DslotLayerStats(NamedTuple):
    name: str
    planes_used: jax.Array       # (Mt, Nt) int32 — digit planes per tile
    n_planes: int
    skipped_frac: jax.Array      # scalar f32 — fraction of planes skipped
    row_planes_used: jax.Array | None = None  # (rows,) f32 effective planes
    planes_bounded: jax.Array | None = None  # (Mt, Nt) int32 — planes never
                                 # issued: static weight-side MSR bound

    @classmethod
    def of(cls, name: str, st: DslotStats) -> "DslotLayerStats":
        return cls(name=name, planes_used=st.planes_used,
                   n_planes=st.n_planes, skipped_frac=st.skipped_frac,
                   row_planes_used=st.row_planes_used,
                   planes_bounded=st.planes_bounded)


def _record(name: str, st: DslotStats) -> None:
    stats_channel.record(f"{name}.skipped_frac", st.skipped_frac)
    stats_channel.record(f"{name}.planes_used_mean",
                         jnp.mean(st.planes_used.astype(jnp.float32)))
    if st.row_planes_used is not None:
        stats_channel.record(f"{name}.row_planes_used", st.row_planes_used)
    if st.planes_bounded is not None:
        stats_channel.record(f"{name}.planes_bounded_mean",
                             jnp.mean(st.planes_bounded.astype(jnp.float32)))


def _resolve_precision(name: str, explicit, static_default):
    """explicit arg > active runtime precision scope > layer static field."""
    if explicit is not None:
        return explicit
    scoped = current_precision(name, None)
    if scoped is not None:
        return scoped
    return static_default


def _rows_precision(n_planes, lead: tuple, rows: int):
    """Broadcast a per-request (B,) budget to the (B*S,) flattened rows."""
    if n_planes is None or not hasattr(n_planes, "ndim"):
        return n_planes
    n_planes = jnp.asarray(n_planes)
    if n_planes.ndim == 1 and lead and n_planes.shape[0] != rows \
            and rows % n_planes.shape[0] == 0:
        n_planes = jnp.repeat(n_planes, rows // n_planes.shape[0])
    return n_planes


@dataclasses.dataclass(frozen=True)
class DslotDense:
    """Dense layer executed on the digit-plane DSLOT engine.

    ``relu=True`` fuses the activation into the kernel and enables per-tile
    early termination (the paper's Algorithm 1); ``relu=False`` (e.g. a
    logits head) runs all planes.  ``use_pallas`` selects the Pallas kernel
    (interpret mode off-TPU) over the vectorized jnp replay — identical
    semantics and identical ``planes_used``, different execution.
    """
    d_in: int
    d_out: int
    name: str = "dslot_dense"
    n_bits: int = 8
    n_planes: int | None = None      # default precision (<= n_bits)
    relu: bool = True
    signed: bool = False             # activation quantization range
    sort_columns: bool = False
    block_m: int = 128
    block_n: int = 128
    block_k: int | None = None       # None = auto VMEM-budget selection
    use_pallas: bool = False
    mesh: object | None = None       # tensor-parallel mesh (N-axis shards)
    tp_axis: str = "model"

    # ------------------------------------------------------------ lifecycle

    def init(self, key, dtype=jnp.float32) -> dict:
        w = jax.random.normal(key, (self.d_in, self.d_out),
                              jnp.float32) * self.d_in ** -0.5
        return self.prepare({"w": w.astype(dtype)})

    def prepare(self, params: dict) -> dict:
        """Attach the one-time prepared state (weight-stationary lowering)."""
        prepared = dslot_prepare(
            params["w"].astype(jnp.float32), n_bits=self.n_bits,
            relu=self.relu, signed=self.signed,
            sort_columns=self.sort_columns, block_m=self.block_m,
            block_n=self.block_n, block_k=self.block_k,
            backend="pallas" if self.use_pallas else "jnp",
            mesh=self.mesh, tp_axis=self.tp_axis)
        return {**params, "dslot": prepared}

    def calibrate(self, params: dict, x_sample: jax.Array) -> dict:
        """Store a fixed activation scale from a calibration batch."""
        prep: DslotWeights = params.get("dslot") or \
            self.prepare(params)["dslot"]
        scale = calibrate_scale(x_sample.reshape(-1, self.d_in),
                                n_bits=self.n_bits, signed=self.signed)
        return {**params, "dslot": prep.with_scale(scale)}

    # ------------------------------------------------------------ execution

    def apply(self, params: dict, x: jax.Array, *, n_planes=None
              ) -> tuple[jax.Array, DslotLayerStats]:
        """x: (..., d_in) -> (..., d_out), plus per-tile plane statistics.

        ``n_planes``: runtime precision — int, i32 scalar, or per-request
        (B,) vector (broadcast over the sequence axis); defaults to the
        active precision scope, then the layer's static field.
        """
        lead = x.shape[:-1]
        flat = x.reshape(-1, self.d_in).astype(jnp.float32)
        prep = params.get("dslot")
        if prep is None:                      # unprepared (legacy) params:
            prep = self.prepare(params)["dslot"]   # trace-time fallback
        npl = _resolve_precision(self.name, n_planes, self.n_planes)
        npl = _rows_precision(npl, lead, flat.shape[0])
        y, st = dslot_execute(prep, flat, n_planes=npl)
        _record(self.name, st)
        return (y.astype(x.dtype).reshape(*lead, self.d_out),
                DslotLayerStats.of(self.name, st))


@dataclasses.dataclass(frozen=True)
class DslotConv2d:
    """2-D convolution lowered to the DSLOT kernel via im2col.

    Input (B, H, W, C), weights (k, k, C, M), valid or same padding.  The
    im2col matrix (B*Ho*Wo, k*k*C) streams through the digit-plane matmul,
    so a "tile" is a block of spatial output positions x output channels —
    the tile-granular analogue of the paper's four-PE pooling group, and
    early termination kills provably-ReLU-dead spatial regions per channel
    block.
    """
    in_channels: int
    out_channels: int
    kernel_size: int
    stride: int = 1
    padding: str = "valid"           # "valid" | "same"
    name: str = "dslot_conv2d"
    n_bits: int = 8
    n_planes: int | None = None
    relu: bool = True
    signed: bool = False
    sort_columns: bool = False
    block_m: int = 128
    block_n: int = 128
    block_k: int | None = None
    use_pallas: bool = False
    mesh: object | None = None       # tensor-parallel mesh (N-axis shards)
    tp_axis: str = "model"

    # ------------------------------------------------------------ lifecycle

    def init(self, key, dtype=jnp.float32) -> dict:
        k, c, m = self.kernel_size, self.in_channels, self.out_channels
        fan_in = k * k * c
        w = jax.random.normal(key, (k, k, c, m), jnp.float32) * fan_in ** -0.5
        return self.prepare({"w": w.astype(dtype)})

    def _kkc(self) -> int:
        return self.kernel_size ** 2 * self.in_channels

    def prepare(self, params: dict) -> dict:
        prepared = dslot_prepare(
            params["w"].astype(jnp.float32).reshape(self._kkc(),
                                                    self.out_channels),
            n_bits=self.n_bits, relu=self.relu, signed=self.signed,
            sort_columns=self.sort_columns, block_m=self.block_m,
            block_n=self.block_n, block_k=self.block_k,
            backend="pallas" if self.use_pallas else "jnp",
            mesh=self.mesh, tp_axis=self.tp_axis)
        return {**params, "dslot": prepared}

    def calibrate(self, params: dict, x_sample: jax.Array) -> dict:
        """Calibrate on sample feature maps (B, H, W, C)."""
        prep: DslotWeights = params.get("dslot") or \
            self.prepare(params)["dslot"]
        cols = im2col(x_sample.astype(jnp.float32), self.kernel_size,
                      self.stride, self.padding)
        scale = calibrate_scale(cols, n_bits=self.n_bits, signed=self.signed)
        return {**params, "dslot": prep.with_scale(scale)}

    # ------------------------------------------------------------ execution

    def apply(self, params: dict, x: jax.Array, *, n_planes=None
              ) -> tuple[jax.Array, DslotLayerStats]:
        """x: (B, H, W, C) -> (B, Ho, Wo, M), plus plane statistics.

        A per-request (B,) ``n_planes`` vector is broadcast over each
        image's Ho*Wo output rows.
        """
        B = x.shape[0]
        k, c, m = self.kernel_size, self.in_channels, self.out_channels
        assert x.shape[-1] == c, (x.shape, c)
        cols = im2col(x.astype(jnp.float32), k, self.stride, self.padding)
        _, Ho, Wo, kkc = cols.shape
        prep = params.get("dslot")
        if prep is None:
            prep = self.prepare(params)["dslot"]
        npl = _resolve_precision(self.name, n_planes, self.n_planes)
        npl = _rows_precision(npl, (B,), B * Ho * Wo)
        y, st = dslot_execute(prep, cols.reshape(B * Ho * Wo, kkc),
                              n_planes=npl)
        _record(self.name, st)
        return (y.astype(x.dtype).reshape(B, Ho, Wo, m),
                DslotLayerStats.of(self.name, st))
