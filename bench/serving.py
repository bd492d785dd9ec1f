"""Set-up, the measured window and the check of one cell, on the program's
public serving API (``ServeEngine`` / ``Request`` / ``ServeConfig``).

The window drives ``ServeEngine.try_add`` and ``ServeEngine.step`` from
one thread as a user's loop would: a client adds its next request when its
last one finished (closed loop), and every token is timestamped on the host clock by ``Request.on_token`` (the
engine has device-got the sampled tokens by then).
"""

from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

import traffic_gen
import weights

clock = time.perf_counter


def model_config(cfg: dict, scale: float | None):
    """The program's ``ModelConfig``; JSON lists (a ``block_pattern``)
    become the tuples the frozen, hashed config holds."""
    from repro.configs.base import DslotConfig, ModelConfig
    d = dict(cfg.get("dslot") or {})
    dslot = DslotConfig(**d, act_scale=scale) if d.get("enabled") \
        else DslotConfig()
    model = {k: tuple(v) if isinstance(v, list) else v
             for k, v in cfg["model"].items()}
    return ModelConfig(**model, dslot=dslot)


def uses_dslot(cfg: dict) -> bool:
    m, d = cfg["model"], cfg.get("dslot") or {}
    return bool(d.get("enabled") and m["act"] == "relu" and not m["glu"])


def act_step(cfg: dict, arch, seed: int) -> float | None:
    if not uses_dslot(cfg):
        return None
    return weights.act_scale(arch, cfg["model"], seed, cfg["calibration"],
                             cfg["dslot"]["n_bits"])


def build_engine(cfg: dict, arch, seed: int, scale: float | None):
    """The served model with the benchmark's weights, behind a
    ``ServeEngine`` built from the configuration's ``ServeConfig``."""
    from repro.models.model_zoo import build_model
    from repro.serve import ServeConfig, ServeEngine

    model = build_model(model_config(cfg, scale))
    params = weights.program_params(model, arch, cfg["model"], seed)
    return ServeEngine(model, params, ServeConfig(**cfg["serve"]))


def warm_up(eng, cfg: dict) -> None:
    """Every shape the window uses: a request per slot with a two-chunk
    prompt (both lanes, a ragged tail, every slot merged) and two tokens of
    pooled decode, drained."""
    from repro.serve import Request

    s = cfg["serve"]
    n = max(s["n_slots"], s["chunks_per_step"])
    for i in range(n):
        eng.try_add(Request(uid=-1 - i, prompt=np.full(
            s["prefill_chunk"] + 1, 1 + i, np.int32), max_new=2))
    eng.drain()
    eng.errors.clear()


@dataclass
class Window:
    """What one measured window saw, on the host clock (seconds)."""
    t0: float
    t1: float
    reqs: dict = field(default_factory=dict)       # uid -> Request
    claimed: dict = field(default_factory=dict)    # uid -> claim step start
    tok_t: dict = field(default_factory=dict)      # uid -> token times
    steps: list = field(default_factory=list)      # (start, end, cpu)
    rejected: int = 0
    trace_t: tuple | None = None                   # (start, stop) if traced


def _span(name: str, traced: bool):
    if traced:
        import jax
        return jax.profiler.TraceAnnotation(name)
    return _NoSpan()


class _NoSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def run_window(eng, cfg: dict, traffic: dict, seed: int, seconds: float,
               settle_tokens: int = 0, trace_dir: str | None = None,
               trace_s: float = 4.0) -> Window:
    """Run the mix's closed loop for ``seconds``; then, sending nothing
    new, step on for at most the mix's ``settle_s`` until the finished
    requests hold ``settle_tokens`` served tokens for the check.  Tokens
    after the close count toward no metric."""
    from repro.serve import Request

    if traffic["loop"] != "closed":
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    vocab = cfg["model"]["vocab_size"]
    dslot = uses_dslot(cfg)
    n_slots = cfg["serve"]["n_slots"]
    stream = traffic_gen.closed_stream(traffic, seed, n_slots)
    pending: set = set()
    w = Window(t0=0.0, t1=0.0)

    def on_token(req, tok, step):
        w.tok_t[req.uid].append(clock())

    def submit(spec):
        req = Request(uid=spec.uid, prompt=traffic_gen.prompt_tokens(
            seed, spec.uid, spec.prompt_len, vocab), max_new=spec.max_new,
            n_planes=spec.budget if dslot else None, tier=spec.tier,
            on_token=on_token)
        w.reqs[req.uid], w.tok_t[req.uid] = req, []
        try:
            ok = eng.try_add(req)
        except ValueError:
            ok = False
        if ok:
            pending.add(req.uid)
        else:
            w.rejected += 1

    def step_once():
        ts, cpu = clock(), time.thread_time()
        with _span("bench.step", traced):
            done = eng.step()
        w.steps.append((ts, clock(), time.thread_time() - cpu))
        for uid in list(pending):
            if w.reqs[uid].phase != "pending":
                w.claimed[uid] = ts
                pending.discard(uid)
        return done

    traced = trace_dir is not None
    tracing = False
    t0 = w.t0 = clock()
    w.t1 = t_end = t0 + seconds
    t_trace = t_end - min(trace_s, seconds / 2)
    for _ in range(n_slots):
        submit(next(stream))
    while eng.live_requests():
        now = clock()
        if now >= t_end:
            break
        if traced and not tracing and now >= t_trace:
            import jax
            jax.profiler.start_trace(trace_dir)
            tracing, w.trace_t = True, (clock(), None)
        for req in step_once():
            if req.uid >= 0:
                with _span("bench.submit", traced):
                    submit(next(stream))
    if tracing:
        import jax
        w.trace_t = (w.trace_t[0], clock())
        jax.profiler.stop_trace()
    settle_end = clock() + float(traffic.get("settle_s", 0))
    while (clock() < settle_end and eng.live_requests()
           and finished_tokens(w) < settle_tokens):
        step_once()
    return w


_EVICTED = ("failed", "quarantined", "timeout", "cancelled")


def p95(values) -> float:
    v = np.asarray(list(values), np.float64)
    return float(np.percentile(v, 95)) if v.size else float("nan")


def _inside(w: Window) -> tuple[int, list]:
    """Tokens emitted in the window, and every gap between consecutive
    tokens of a request that both landed in it."""
    n_tok, gaps = 0, []
    for ts in w.tok_t.values():
        inside = [t for t in ts if w.t0 <= t <= w.t1]
        n_tok += len(inside)
        gaps.extend(np.diff(inside).tolist())
    return n_tok, gaps


def end_to_end(w: Window) -> dict:
    """Output rate and inter-token gap tail of the window."""
    n_tok, gaps = _inside(w)
    return {"output_tok_s": n_tok / (w.t1 - w.t0),
            "itl_p95_ms": p95(gaps) * 1e3,
            "tokens": n_tok, "gaps": len(gaps)}


def finished_tokens(w: Window) -> int:
    return sum(len(r.out) for r in w.reqs.values() if r.phase == "done")


def percentiles(w: Window, qs) -> list[float]:
    gaps = _inside(w)[1]
    return [float(np.percentile(gaps, q)) for q in qs] if gaps else []


def step_summary(w: Window) -> str:
    """Median and slowest ``step()`` calls of the window, when the slowest
    began and the CPU time its thread spent in it, and the longest host time
    between two calls: where a run that reads low lost time."""
    inside = [s for s in w.steps if w.t0 <= s[0] and s[1] <= w.t1]
    if not inside:
        return "none"
    dur = np.array([b - a for a, b, _ in inside]) * 1e3
    k = int(np.argmax(dur))
    between = [b[0] - a[1] for a, b in zip(inside, inside[1:])] or [0.0]
    return (f"{len(dur)} steps, median {np.median(dur):.2f}, slowest "
            + " ".join(f"{v:.1f}" for v in np.sort(dur)[-5:][::-1])
            + f" (the slowest {inside[k][0] - w.t0:.2f}s into the window, "
            f"step {k}, thread CPU {inside[k][2] * 1e3:.1f})"
            + f", over 2x median {int(np.sum(dur > 2 * np.median(dur)))}"
            f", longest between steps {max(between) * 1e3:.2f}")


def failed(w: Window) -> int:
    return w.rejected + sum(r.phase in _EVICTED for r in w.reqs.values())


def check(cfg: dict, arch, traffic: dict, seed: int, w: Window,
          min_tokens: int, scale: float | None,
          with_control: bool = False) -> dict:
    """The reference's readings over a sample of the window's finished
    requests (``sample_for_check``), each at its granted plane budget."""
    import reference
    sample = [(r.prompt, list(r.out), r.n_planes or 8)
              for r in sample_for_check(w, seed, min_tokens)]
    return reference.compare(
        arch, cfg["model"], cfg["dslot"] if uses_dslot(cfg) else None, seed,
        sample, pad_to=-(-traffic_gen.max_total(traffic) // 128) * 128,
        step=scale or 1.0, with_control=with_control) | {
            "requests": len(sample)}


def sample_for_check(w: Window, seed: int, min_tokens: int) -> list:
    """Finished requests drawn from the seed, the longest first, until they
    hold ``min_tokens`` served tokens."""
    done = [r for r in w.reqs.values() if r.phase == "done" and r.uid >= 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.out), r.uid))
    rest = [r for r in done if r is not longest]
    order = traffic_gen._rng(seed, 7).permutation(len(rest))
    picked, n = [longest], len(longest.out)
    for i in order:
        if n >= min_tokens:
            break
        picked.append(rest[i])
        n += len(rest[i].out)
    return picked


def work_in(w: Window, arch, m: dict, lo: float, hi: float) -> float:
    """Model operations, as the architecture module counts them, of the
    tokens emitted and prompts claimed in [lo, hi]: the prompt when it was
    claimed into a lane, each output token at its context length."""
    ops = 0.0
    for uid, req in w.reqs.items():
        c = w.claimed.get(uid)
        if c is not None and lo <= c <= hi:
            ops += arch.prefill_flops(m, len(req.prompt))
        for j, t in enumerate(w.tok_t[uid]):
            if lo <= t <= hi and j > 0:
                ops += arch.decode_token_flops(m, len(req.prompt) + j)
    return ops


@dataclass
class RunRecord:
    """Everything a per-layer reader may read."""
    cell: str
    cfg: dict
    traffic: dict
    peaks: dict
    window: Window
    trace: object = None            # devtrace.Trace, when traced
    results: list = field(default_factory=list)   # GenerateResults
    arch: object = None             # the configuration's arch module

    @property
    def model(self) -> dict:
        return self.cfg["model"]

    def rows(self, module_name: str) -> int | None:
        """Rows of the MLP call inside a compiled program of the engine."""
        s = self.cfg["serve"]
        if "decode" in module_name:
            return s["n_slots"]
        if "extend" in module_name:
            return s["chunks_per_step"] * s["prefill_chunk"]
        return None


def free(eng) -> None:
    """Drop the engine's device state so the reference has the chip."""
    eng.close()
    for attr in ("params", "state"):
        setattr(eng, attr, None)
    eng.pipeline = None
    gc.collect()


def results_of(w: Window) -> list:
    return [r.result for r in w.reqs.values()
            if r.result is not None and r.phase == "done"]
