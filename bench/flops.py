"""Operations and bytes that each counted piece of work needs, from shapes.

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


def matmul_params(m: dict) -> tuple[int, int]:
    """(parameters of the matmuls in all layers, of the output head)."""
    d, hd = m["d_model"], m.get("head_dim") or m["d_model"] // m["n_heads"]
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    mlp = d * m["d_ff"] * (3 if m["glu"] else 2)
    return m["n_layers"] * (attn + mlp), d * m["vocab_size"]


def attention_flops(m: dict, ctx: int) -> float:
    """Scores and value mixing of one query against ``ctx`` keys."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return 4.0 * m["n_layers"] * ctx * m["n_heads"] * hd


def decode_token_flops(m: dict, ctx: int) -> float:
    """One generated token whose query sees ``ctx`` keys (itself included)."""
    body, head = matmul_params(m)
    return 2.0 * (body + head) + attention_flops(m, ctx)


def prefill_flops(m: dict, prompt_len: int) -> float:
    """A whole prompt: every position through the layers, causal attention,
    and the head once (only the last position's logits are needed)."""
    body, head = matmul_params(m)
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    causal_pairs = prompt_len * (prompt_len + 1) / 2.0
    attn = 4.0 * m["n_layers"] * causal_pairs * m["n_heads"] * hd
    return 2.0 * body * prompt_len + attn + 2.0 * head
