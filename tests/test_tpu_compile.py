"""The digit-plane kernel compiles for a TPU v5e at the widths it serves.

Each test lowers and compiles for one chip of a described ``v5e:2x2``
topology (no chip attached: the TPU compiler is installed, nothing runs)
and asserts that the program holds the Mosaic kernel (``tpu_custom_call``),
not an interpreted loop.  Widths are the up-projections ``chip_smoke.py``
runs on the chip: olmo-1b (K=2048, N=8192) and seamless-m4t-medium
(K=1024, N=4096), at decode (8) and prefill (2048) rows; one whole
``generate`` program at a reduced width holds the kernel inside the model.
Interpret-mode tests cannot see a block Mosaic refuses; these can.  The
serving engine's pooled decode program, at both benchmark cells' widths,
holds no whole-ring temporary and writes its KV pool in place.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a test worker that is not given this
file must collect the same tests without loading it.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import DslotConfig, ModelConfig
from repro.configs.registry import ARCHS
from repro.kernels.dslot_matmul import dslot_matmul_pallas
from repro.kernels.ops import dslot_execute, dslot_prepare
from repro.models.model_zoo import build_model
from repro.serve import generate
from repro.serve.engine import decode_program

OLMO = (2048, 8192)
SEAMLESS = (1024, 4096)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Code that asks ``jax.default_backend()`` takes its TPU branch: the
    described chip is not the process's backend."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("width,M,budget", [
    (OLMO, 8, "scalar"),
    (OLMO, 2048, "per-row"),
    (SEAMLESS, 2048, "per-row"),
], ids=["olmo-decode-scalar", "olmo-prefill-rows", "seamless-prefill-rows"])
def test_execute_compiles_for_v5e(one_chip, on_tpu, width, M, budget):
    K, N = width
    prep = jax.eval_shape(
        lambda w: dslot_prepare(w, n_bits=8, relu=True, signed=True,
                                sort_columns=True, backend="pallas",
                                x_scale=jnp.float32(0.05)),
        jax.ShapeDtypeStruct((K, N), jnp.float32))
    prep = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), prep)
    npl = _sds((M,) if budget == "per-row" else (), jnp.int32, one_chip)
    compiled = jax.jit(lambda p, x, n: dslot_execute(p, x, n_planes=n)) \
        .lower(prep, _sds((M, K), jnp.float32, one_chip), npl).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("wdtype,block_k", [
    (jnp.float32, 512),
    (jnp.bfloat16, None),
], ids=["k-tiled-512", "bf16-weights"])
def test_kernel_compiles_for_v5e(one_chip, on_tpu, wdtype, block_k):
    # the default ``interpret=None`` resolves to the compiled kernel on TPU
    K, N = OLMO
    M = 2048
    compiled = jax.jit(lambda q, w, n, b: dslot_matmul_pallas(
        q, w, block_k=block_k, n_planes_rt=n, row_budget=b)).lower(
            _sds((M, K), jnp.uint8, one_chip), _sds((K, N), wdtype, one_chip),
            _sds((), jnp.int32, one_chip),
            _sds((M,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


def test_kernel_call_carries_its_trace_name(one_chip, on_tpu):
    # the benchmark reads the kernel's device time by this name
    K, N = OLMO
    compiled = jax.jit(lambda q, w: dslot_matmul_pallas(q, w).out).lower(
        _sds((128, K), jnp.uint8, one_chip),
        _sds((K, N), jnp.float32, one_chip)).compile()
    calls = [line for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    assert all(re.match(r"\s*(ROOT )?%dslot_matmul_pallas(\.\d+)? = ", line)
               for line in calls), calls


def test_dslot_generate_compiles_for_v5e(one_chip, on_tpu):
    # a whole ``generate`` program: prefill builds the KV ring that the
    # decode loop then writes (the TPU compiler aborts on a ring built by
    # scatter here), and every MLP runs the compiled kernel
    cfg = dataclasses.replace(
        ARCHS["olmo-1b"].reduced(), dtype="bfloat16", act="relu", glu=False,
        dslot=DslotConfig(enabled=True, use_pallas=True, act_scale=0.03))
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: model.prepare_dslot(model.init(jax.random.PRNGKey(0))))
    sds = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), params)
    compiled = jax.jit(lambda p, t, n: generate(
        model, p, {"tokens": t}, 4, n_planes=n).tokens).lower(
            sds, _sds((4, 16), jnp.int32, one_chip),
            _sds((4,), jnp.int32, one_chip)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("blocks,shape,name", [
    (dict(block_m=32, block_n=32), (32, 64, 32), "block_n=32"),
    (dict(block_m=12, block_n=128), (24, 64, 128), "block_m=12"),
    (dict(block_m=128, block_n=128, block_k=96), (128, 192, 128),
     "block_k=96"),
])
def test_unaligned_block_refused_when_compiled(blocks, shape, name):
    # compiled, a block Mosaic cannot tile is refused by name before
    # lowering; the interpreter (the CPU tests) takes the same blocks
    M, K, N = shape
    q = jnp.ones((M, K), jnp.uint8)
    w = jnp.full((K, N), 0.01, jnp.float32)
    with pytest.raises(ValueError, match=name):
        dslot_matmul_pallas(q, w, interpret=False, **blocks)
    out = dslot_matmul_pallas(q, w, interpret=True, **blocks).out
    assert jnp.allclose(out, 0.01 * K)


# The benchmark cells' published widths (OPT-1.3b, arXiv:2205.01068; OLMo-1B,
# arXiv:2402.00838), each with its slot count and context, as the engine
# serves them.
OPT_1P3B = dict(
    name="opt-1.3b", family="dense", n_layers=24, d_model=2048, n_heads=32,
    n_kv_heads=32, head_dim=64, d_ff=8192, vocab_size=50272, qkv_bias=True,
    norm="layernorm", act="relu", glu=False, tie_embeddings=True,
    scan_unroll=2, dslot=DslotConfig(enabled=True, use_pallas=True,
                                     block_m=128, block_n=128,
                                     act_scale=0.03))
OLMO_1B = dict(
    name="olmo-1b", family="dense", n_layers=16, d_model=2048, n_heads=16,
    n_kv_heads=16, head_dim=128, d_ff=8192, vocab_size=50304,
    norm="nonparam_ln", act="silu", glu=True, tie_embeddings=True)


@pytest.mark.parametrize("widths,slots", [(OPT_1P3B, 8), (OLMO_1B, 16)],
                         ids=["opt-1.3b", "olmo-1b"])
def test_pooled_decode_writes_the_ring_in_place(one_chip, on_tpu, widths,
                                                slots):
    # one decode step reads each layer's ring where it lies and writes only
    # the new tokens: no temporary as large as one layer's K ring, and the
    # donated pool's K and V come back aliased to their input
    max_len = 2048
    cfg = ModelConfig(**widths)
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    if cfg.dslot.enabled:
        params = jax.eval_shape(model.prepare_dslot, params)
    state = jax.eval_shape(lambda: model.init_decode_state(slots, max_len))
    sds = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip),
                       (params, state))
    compiled = decode_program(model, slots).lower(
        *sds, _sds((slots, 1), jnp.int32, one_chip),
        _sds((slots,), jnp.int32, one_chip)).compile()
    mem = compiled.memory_analysis()
    ring = slots * max_len * cfg.n_kv_heads * cfg.head_dim * 2   # bf16 K
    assert mem.temp_size_in_bytes < ring, mem
    assert mem.alias_size_in_bytes >= 2 * cfg.n_layers * ring, mem
    if cfg.dslot.enabled:
        _assert_kernel(compiled)
