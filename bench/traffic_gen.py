"""One general traffic generator, driven by the parameter files in ``traffic/``.

Every seed gets the same multiset of request sizes and plane budgets,
drawn once from a fixed generator, in an order of its own; the prompt token
ids come from the seed.  The order is shuffled within blocks of one request
per client, so every seed sends the same sizes in each block (the first
round's prompts among them), and two seeds offer the same work over any
window and differ only in order and content, which keeps run-to-run spread
down to what the system does with the work.

Closed loop (``"loop": "closed"``): one client per pool slot, each sending
its next request when the last one finished.  With ``"first_round":
"ramp"`` client ``k``'s first request asks for ``(k + 1) / clients`` of the
shortest output the mix draws, at the ``k``-th plane budget in turn, the
same for every seed, so completions are staggered from the start as in a
loop that has run for a while and the requests that finish first, which
the check reads, hold every budget; every later request is the next entry
of the seed's order.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

SIZE_SEED = 20231011        # fixed: the multiset of sizes is seed-independent


@dataclass
class Spec:
    uid: int
    prompt_len: int
    max_new: int
    budget: int
    tier: str


def _rng(seed: int, stream: int) -> np.random.Generator:
    s = int(seed) % (1 << 64)
    return np.random.default_rng([s & 0xFFFFFFFF, s >> 32, stream])


def _draw(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if dist["dist"] == "lognormal":
        v = dist["median"] * np.exp(dist["sigma"] * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        v = rng.uniform(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.floor(v), dist["min"], dist["max"]).astype(np.int64)


def _pool(traffic: dict, n: int) -> list[Spec]:
    """The fixed multiset: ``n`` sizes with budgets in equal shares."""
    rng = np.random.default_rng(SIZE_SEED)
    plen = _draw(traffic["prompt_tokens"], n, rng)
    olen = _draw(traffic["output_tokens"], n, rng)
    budgets, tiers = traffic["plane_budgets"], traffic["tiers"]
    return [Spec(uid=i, prompt_len=int(plen[i]), max_new=int(olen[i]),
                 budget=int(budgets[i % len(budgets)]),
                 tier=tiers[i % len(tiers)]) for i in range(n)]


def closed_stream(traffic: dict, seed: int, clients: int):
    """Endless request specs for a closed loop of ``clients`` clients."""
    n = int(traffic.get("pool", 4096))
    specs = _pool(traffic, n)
    rng = _rng(seed, 1)
    order = np.concatenate([b + rng.permutation(min(clients, n - b))
                            for b in range(0, n, clients)])
    ramp = traffic.get("first_round") == "ramp"
    budgets = traffic["plane_budgets"]
    shortest = traffic["output_tokens"]["min"]
    k = 0
    while True:
        s = specs[order[k % n]]
        max_new, budget, tier = s.max_new, s.budget, s.tier
        if ramp and k < clients:
            max_new = max(1, shortest * (k + 1) // clients)
            budget = budgets[k % len(budgets)]
            tier = traffic["tiers"][k % len(budgets)]
        yield Spec(uid=k, prompt_len=s.prompt_len, max_new=max_new,
                   budget=budget, tier=tier)
        k += 1


def prompt_tokens(seed: int, uid: int, length: int, vocab: int) -> np.ndarray:
    """The prompt of request ``uid``: token ids drawn from the seed."""
    return _rng(seed, 1000 + uid).integers(0, vocab, length).astype(np.int32)


def max_total(traffic: dict) -> int:
    """Longest prompt plus longest output the mix can send."""
    return traffic["prompt_tokens"]["max"] + traffic["output_tokens"]["max"]
