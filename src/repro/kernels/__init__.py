"""TPU hot-spot kernels: the digit-serial DSLOT matmul.

``dslot_matmul.py`` — pl.pallas_call kernel (fused in-kernel MSDF digit
encoding straight from the quantized activation block — no materialized
(D, M, K) plane tensor — K-chunked VMEM streaming with a chunk-aware
per-tile early-termination bound, SMEM runtime precision scalar, VMEM per-row
budget column, SMEM static per-N-tile weight-side MSR plane bound, auto
block-size selection, bf16 weights, batched entry);
``ops.py`` — jit'd wrapper with quantization / padding / column-sorting and
a jnp backend replaying identical termination accounting plane-free;
``ref.py`` — pure-jnp oracle the kernels are tested against
(tests/test_kernels.py, tests/test_ktiling.py, tests/test_fused_digits.py).
"""

from .dslot_matmul import (DslotMatmulOut, colsum_tables,
                           dslot_matmul_pallas, dslot_matmul_pallas_batched,
                           q_storage_dtype, select_block_k)
from .ops import (DslotStats, DslotWeights, calibrate_scale, dslot_execute,
                  dslot_matmul, dslot_prepare, prepare_call_count,
                  quantize_activations)
from .ref import csd_matmul_ref, dslot_matmul_ref, make_planes, sd_digit_plane

__all__ = ["DslotMatmulOut", "DslotStats", "DslotWeights", "dslot_matmul",
           "dslot_prepare", "dslot_execute", "calibrate_scale",
           "prepare_call_count", "dslot_matmul_pallas",
           "dslot_matmul_pallas_batched", "colsum_tables", "select_block_k",
           "q_storage_dtype", "quantize_activations", "dslot_matmul_ref",
           "csd_matmul_ref", "make_planes", "sd_digit_plane"]
