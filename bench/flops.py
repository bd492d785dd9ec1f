"""Operations and bytes that each counted piece of work needs, from shapes,
and the least time the chip could take for them.  A whole token's model
operations are the architecture module's (``arch/<name>.py``,
``decode_token_flops`` and ``prefill_flops``).

These are the work the model requires, not what an implementation happens
to do: a kernel that pads rows, re-streams weights per digit plane or runs
in f32 is credited only with the dense bf16 work of the call it replaces.
"""

from __future__ import annotations


def up_proj_work(m: int, k: int, n: int, itemsize: int = 2
                 ) -> tuple[float, float]:
    """The MLP up-projection ``(m, k) @ (k, n)`` at the rows the layer
    receives (not padded to a block): ``2 m k n`` operations; the weights
    read once plus the rows in and out, all in the model's dtype."""
    flops = 2.0 * m * k * n
    nbytes = float(itemsize) * (k * n + m * k + m * n)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    t_compute = flops / peaks["bf16_flops"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
