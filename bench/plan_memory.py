"""Device memory of a configuration's serving programs, compiled for a
described TPU v5e with no chip attached.

    JAX_PLATFORMS=cpu python bench/plan_memory.py <config> [n_slots ...]

For each pool width it compiles the engine's two programs at the
configuration's widths (the pooled decode step over ``n_slots`` rows, the
engine's own ``decode_program``, and the batched lane extend over
``chunks_per_step`` lanes of ``prefill_chunk`` tokens) and prints
``compiled.memory_analysis()`` with an estimate of the step's peak: the
program's arguments, outputs and temporaries, less what its outputs alias
(the decode step donates its pool, so the pool it returns is the one it
was given), plus the decode state that stays live beside it (the lane
pool beside a decode step, the slot pool beside an extend).
Nothing runs, so it is a plan; ``memory_peak_bytes`` of a chip run is the
measurement.
"""

from __future__ import annotations

import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def _bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def plan(config: str, pools: list[int]) -> list[dict]:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import serving
    from repro.models.model_zoo import build_model
    from repro.runtime import precision_scope
    from repro.serve.engine import decode_program

    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        cfg = json.load(f)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    model = build_model(serving.model_config(
        cfg, 0.05 if serving.uses_dslot(cfg) else None))
    jax.default_backend = lambda: "tpu"   # the described chip's branch
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if serving.uses_dslot(cfg):
        params = jax.eval_shape(model.prepare_dslot, params)
    s = cfg["serve"]

    def sds(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
            tree)

    def extend(p, st, t, lens, npl):
        with precision_scope(npl):
            return model.extend(p, st, t, lengths=lens)

    lanes = s["chunks_per_step"]
    lane_st = jax.eval_shape(lambda: model.init_decode_state(
        lanes, s["max_len"]))
    fresh = jax.eval_shape(lambda: model.init_decode_state(1, s["max_len"]))
    i32 = jnp.int32
    ext = jax.jit(extend).lower(
        sds(params), sds(lane_st),
        jax.ShapeDtypeStruct((lanes, s["prefill_chunk"]), i32, sharding=chip),
        jax.ShapeDtypeStruct((lanes,), i32, sharding=chip),
        jax.ShapeDtypeStruct((lanes,), i32, sharding=chip)).compile()
    em = ext.memory_analysis()
    out = []
    for n in pools:
        pool = jax.eval_shape(lambda: model.init_decode_state(n, s["max_len"]))
        dec = decode_program(model, n).lower(
            sds(params), sds(pool),
            jax.ShapeDtypeStruct((n, 1), i32, sharding=chip),
            jax.ShapeDtypeStruct((n,), i32, sharding=chip)).compile()
        dm = dec.memory_analysis()
        side = _bytes(fresh)
        d_peak = (dm.argument_size_in_bytes + dm.output_size_in_bytes
                  - dm.alias_size_in_bytes + dm.temp_size_in_bytes
                  + _bytes(lane_st) + side)
        e_peak = (em.argument_size_in_bytes + em.output_size_in_bytes
                  - em.alias_size_in_bytes + em.temp_size_in_bytes
                  + _bytes(pool) + side)
        out.append({"config": config, "n_slots": n,
                    "params_bytes": _bytes(params),
                    "pool_bytes": _bytes(pool),
                    "decode": {"args": dm.argument_size_in_bytes,
                               "out": dm.output_size_in_bytes,
                               "alias": dm.alias_size_in_bytes,
                               "temp": dm.temp_size_in_bytes},
                    "extend": {"args": em.argument_size_in_bytes,
                               "out": em.output_size_in_bytes,
                               "alias": em.alias_size_in_bytes,
                               "temp": em.temp_size_in_bytes},
                    "peak_estimate_bytes": max(d_peak, e_peak)})
    return out


if __name__ == "__main__":
    pools = [int(a) for a in sys.argv[2:]] or [8, 16]
    for row in plan(sys.argv[1], pools):
        print(json.dumps(row), flush=True)
