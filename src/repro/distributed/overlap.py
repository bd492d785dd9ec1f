"""Collective-matmul: ppermute-pipelined TP all-gather overlapped with MXU.

The canonical GSPMD lowering of a column-parallel matmul with a
sequence-sharded activation is ``all-gather(x) ; dot`` — the gather sits on
the critical path.  The collective-matmul schedule (Wang et al., ASPLOS'23)
decomposes it into TP rounds:

    round r on device d:  y[rows of slice (d+r) % n, own N-cols] = cur @ W_d
                          cur <- ppermute(cur)     (next x slice arrives
                                                    while this matmul runs)

so each ICI hop hides behind one matmul slice.  Implemented with shard_map —
the per-device program is explicit and XLA schedules the ppermute
asynchronously on real TPUs.

Layouts:  x (S, K) sharded P(axis, None) — sequence-sharded activation;
          w (K, N) sharded P(None, axis) — column-parallel weight;
          y (S, N) sharded P(None, axis).
Bit-identical (up to f32 accumulation) to the plain lowering; equivalence is
tested on an 8-device CPU mesh.  Used as a §Perf hillclimb for
collective-bound cells.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def collective_matmul_ag(x, w, mesh: Mesh, axis: str = "model"):
    """Pipelined all-gather matmul (see module docstring)."""
    n = mesh.shape[axis]

    def body(xl, wl):                       # xl: (S/n, K), wl: (K, N/n)
        idx = jax.lax.axis_index(axis)
        s_local = xl.shape[0]
        y0 = jax.lax.pvary(jnp.zeros((s_local * n, wl.shape[1]), jnp.float32),
                           (axis,))         # device-varying accumulator
        # device i sends to i-1: after r rounds, device d holds slice (d+r)%n
        perm = [(i, (i - 1) % n) for i in range(n)]

        def round_step(carry, r):
            y, cur = carry
            src = (idx + r) % n
            part = jnp.einsum("sk,kn->sn", cur.astype(jnp.float32),
                              wl.astype(jnp.float32))
            y = jax.lax.dynamic_update_slice(y, part, (src * s_local, 0))
            cur = jax.lax.ppermute(cur, axis, perm)
            return (y, cur), None

        (y, _), _ = jax.lax.scan(round_step, (y0, xl),
                                 jnp.arange(n, dtype=jnp.int32))
        return y

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis, None), P(None, axis)),
                         out_specs=P(None, axis))(x, w)


def plain_matmul_ag(x, w, mesh: Mesh, axis: str = "model"):
    """Reference: the unpipelined lowering (all-gather then one big dot)."""

    def body(xl, wl):
        xg = jax.lax.all_gather(xl, axis, axis=0, tiled=True)
        return jnp.einsum("sk,kn->sn", xg.astype(jnp.float32),
                          wl.astype(jnp.float32))

    return jax.shard_map(body, mesh=mesh,
                         in_specs=(P(axis, None), P(None, axis)),
                         out_specs=P(None, axis))(x, w)
