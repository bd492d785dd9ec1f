"""Serving engine: slot-pool continuous batching with a chunked-prefill
admission pipeline, DSLOT digit-serial execution mode, per-request QoS
tiers under an optional SLO control loop, and streaming token output.

``generate`` is the simple batch API (prefill once, decode N tokens); it
returns a :class:`repro.serve.result.GenerateResult` — tokens plus the
per-request planes-executed account when the DSLOT path is on.  The old
``return_stats=True`` tuple form still works through a deprecation shim.

``ServeEngine`` is the production shape: a fixed pool of B slots; decode
steps advance every live slot together (one jitted step for the whole
pool), finished slots free up immediately.  Construction takes exactly
``(model, params, cfg: ServeConfig)`` — pool geometry, admission knobs,
sampler, precision policy and SLO config all live on the config (the old
``n_slots=``/``max_len=``/``sample=``/``precision_policy=``/
``serve_config=`` keywords are mapped onto a config by a warn-once
deprecation shim).  Admission is NON-BLOCKING and BATCHED: ``try_add`` only
validates and enqueues; the engine's step loop interleaves one batched
admission forward per decode step — up to ``ServeConfig.chunks_per_step``
PREFILLING requests each advance by one fixed-size ``prefill_chunk`` of
prompt, stacked into a single ragged-offset forward (executed by
``repro.serve.prefill.PrefillPipeline``) — so admitting long prompts never
stalls the pool for a full-prompt forward, and a burst of admissions drains
``chunks_per_step`` prompts at a time.  A request moves through PENDING ->
PREFILLING -> DECODING -> DONE (``Request.phase``), and its slot joins the
pooled decode the very step its last prompt chunk lands.

Streaming: every emitted token is pushed through ``Request.on_token`` (when
set) the step it is sampled, and ``Request.token_steps`` records the engine
step of each token — so TTFT and inter-token latency are externally
observable per token, not just engine-internal counters.
``ServeEngine.stream(req)`` wraps both as a generator handle that drives
the engine and yields tokens as they land.

Per-slot position vectors (threaded through the model's per-sequence
KV-cache ring) make the batch composition fully dynamic without
recompilation — merging a finished prefill into a non-empty pool never
disturbs other slots' decode positions, and chunked admission stays
token-exact versus a solo ``generate`` of the same prompt (in DSLOT mode
this additionally requires a calibrated ``DslotConfig.act_scale``: the
per-call-max quantization fallback is not invariant to how a prompt is
split into chunks — ``try_add`` REJECTS budgeted multi-chunk admissions on
an uncalibrated model instead of silently drifting; see ``kernels/ops.py``
and ``docs/serving.md``).

Hardening (``docs/serving.md``, "Failure modes and recovery"): ``step()``
NEVER raises.  Exceptions from admission or decode forwards are absorbed
with bounded retry (``ServeConfig.max_step_retries``) and logged to
``ServeEngine.errors``; state commits are transactional, so a failed step
leaves queue/slots/lanes exactly where they were and
``ServeEngine.check_invariants()`` (``serve/health.py``) passes after every
tick.  Non-finite logit rows quarantine exactly the poisoned slot
(``phase == "quarantined"``) — surviving co-batched requests keep their
bit-exact token streams, the same isolation bar as cancel-mid-batch.
Per-request deadlines (``Request.deadline_steps`` /
``ServeConfig.default_deadline_steps``) evict overdue requests wherever
they are (``phase == "timeout"``) and feed the SLO controller as pressure.
``drain()``/``close()`` give a graceful shutdown path, and the whole
failure surface is exercisable on demand through the deterministic fault
plane in ``serve/faults.py`` (``ServeConfig.faults``).

DSLOT serving mode (``cfg.dslot.enabled`` + ReLU MLPs): the engine prepares
the model's weight-stationary plane tables ONCE at construction
(``Model.prepare_dslot``), every request carries its own digit-plane budget
(explicit ``Request.n_planes`` or assigned by a ``repro.runtime`` precision
policy at enqueue time), prefill chunks and the pooled decode step execute
each request's rows at that request's precision (a runtime argument — no
retrace across precisions), and the per-request planes-executed account is
fed back to the policy when the request finishes (the ``AdaptiveBudget``
loop).  With ``ServeConfig.slo`` set, a ``repro.serve.slo.SloController``
additionally clamps every slot's budget to its QoS tier's current plane
level each step — shedding planes under burst, restoring them under slack
— which is the load side of the paper's run-time-tunable precision.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from dataclasses import dataclass, field
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
from jax.profiler import StepTraceAnnotation, TraceAnnotation
from jax.sharding import AxisType, NamedSharding, PartitionSpec
import numpy as np

from repro.kernels.ops import DslotWeights
from repro.models import stats as stats_channel
from repro.models.attention import cache_capacity
from repro.models.mlp import mlp_uses_dslot
from repro.models.model_zoo import Model
from repro.runtime import PolicyFeedback, precision_scope
from repro.serve.config import ServeConfig
from repro.serve.faults import FaultInjector
from repro.serve.prefill import (CANCELLED, DECODING, DONE, FAILED,
                                 PREFILLING, QUARANTINED, TIMEOUT,
                                 PrefillPipeline, _batch_axes, _lane_ops)
from repro.serve.result import GenerateResult
from repro.serve.slo import STANDARD, TIERS, SloController, SloSignals

_ROWKEY = "mlp_up_dslot.row_planes_used"
_BNDKEY = "mlp_up_dslot.planes_bounded_mean"

# one DeprecationWarning per legacy surface per process — enough to nudge a
# migration without drowning a driving loop in repeats
_LEGACY_WARNED: set[str] = set()


def _warn_once(key: str, msg: str) -> None:
    if key in _LEGACY_WARNED:
        return
    _LEGACY_WARNED.add(key)
    warnings.warn(msg, DeprecationWarning, stacklevel=3)


def greedy_sample(logits: jax.Array, key=None) -> jax.Array:
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def temperature_sample(logits: jax.Array, key, temp: float = 0.8) -> jax.Array:
    return jax.random.categorical(key, logits / temp, axis=-1).astype(jnp.int32)


def _collapse_rows(sink: dict, batch: int) -> jax.Array | None:
    """Average the per-row planes-executed records of every DSLOT MLP call
    into one (B,) vector.  Records may be (B,) (plain layers) or carry
    leading stack axes from scan-over-layers; collapse those by mean."""
    vals = []
    for v in sink.get(_ROWKEY, []):
        v = jnp.asarray(v, jnp.float32)
        while v.ndim > 1:
            v = v.mean(axis=0)
        if v.shape == (batch,):
            vals.append(v)
    if not vals:
        return None
    return jnp.mean(jnp.stack(vals), axis=0)


def _collapse_bounded(sink: dict) -> jax.Array | None:
    """Mean weight-side never-issued planes per tile across the step's DSLOT
    MLP calls (scalar — the static MSR bound is request-independent)."""
    vals = [jnp.mean(jnp.asarray(v, jnp.float32))
            for v in sink.get(_BNDKEY, [])]
    if not vals:
        return None
    return jnp.mean(jnp.stack(vals))


def _sum_records(sink: dict, key: str) -> jax.Array | None:
    """Sum of every record under ``key`` (scan-stacked ones included)."""
    vals = [jnp.sum(jnp.asarray(v, jnp.float32)) for v in sink.get(key, [])]
    return jnp.sum(jnp.stack(vals)) if vals else None


def decode_program(model: Model, n_slots: int):
    """The engine's pooled decode step, jitted as ``jit__decode``:
    ``(params, state, tokens (n_slots, 1), budgets (n_slots,)) -> (logits,
    state, aux)``.  The state is donated: each ring's new tokens are written
    in place (the output aliases the input), so the caller must drop the
    state it passed and keep the one returned."""

    def _decode(p, st, t, npl):
        with stats_channel.collect() as sink, precision_scope(npl):
            lg, st2 = model.decode_step(p, st, t)
        rows = _collapse_rows(sink, n_slots)
        bnd = _collapse_bounded(sink)
        aux = {} if rows is None else {"rows": rows}
        if bnd is not None:
            aux["bounded"] = bnd
        routed = _sum_records(sink, "moe_rows_routed")
        if routed is not None:
            # the expert layers' row use: rows routed to held experts, and
            # rows their matmuls computed
            aux["moe_rows"] = jnp.stack(
                [routed, _sum_records(sink, "moe_rows_computed")])
        # per-slot non-finite detection, fused into the step (one reduce)
        # — the quarantine guard reads it on the host
        aux["finite"] = jnp.all(jnp.isfinite(lg), axis=-1)
        return lg, st2, aux

    return jax.jit(_decode, donate_argnums=(1,))


def generate(model: Model, params, batch: dict, max_new_tokens: int,
             *, max_len: int | None = None, sample=greedy_sample,
             key=None, n_planes=None, return_stats: bool | None = None
             ) -> GenerateResult:
    """Prefill + greedy/temperature decode.  Returns a ``GenerateResult``
    (``.tokens`` is (B, max_new_tokens); the DSLOT planes-executed account
    rides along when the digit-serial path is on).

    ``n_planes``: runtime DSLOT precision — int or per-request (B,) i32
    vector (ignored unless the model's digit-serial MLP path is enabled).

    ``return_stats`` is DEPRECATED: ``True`` returns the legacy
    ``(tokens, stats_dict)`` tuple, ``False`` the bare tokens array — both
    warn once.  Leave it unset for the ``GenerateResult``.
    """
    if return_stats is not None:
        _warn_once(
            "generate.return_stats",
            "generate(return_stats=...) is deprecated; generate() now "
            "returns a GenerateResult — use .tokens / .planes_used_mean / "
            ".skipped_frac")
    B, S = batch["tokens"].shape
    if model.cfg.frontend and "frontend" in batch:
        S += batch["frontend"].shape[1]
    max_len = max_len or (S + max_new_tokens)
    if n_planes is not None:
        n_planes = jnp.asarray(n_planes, jnp.int32)
        if n_planes.ndim == 0:
            n_planes = jnp.full((B,), n_planes, jnp.int32)
    # stats collection is trace-time gated (no dead work when off): on by
    # default exactly when the DSLOT path can produce them
    want_stats = mlp_uses_dslot(model.cfg) if return_stats is None \
        else bool(return_stats)

    with precision_scope(n_planes):
        logits, state = model.prefill(params, batch, max_len=max_len)
        tok = sample(logits) if key is None else sample(logits, key)

        def step(carry, _):
            tok, state, key = carry
            if want_stats:
                with stats_channel.collect() as sink:
                    lg, state = model.decode_step(params, state, tok[:, None])
                rows = _collapse_rows(sink, B)
                bnd = _collapse_bounded(sink)
                st = {} if rows is None else {"rows": rows}
                if bnd is not None:
                    st["bounded"] = bnd
            else:
                lg, state = model.decode_step(params, state, tok[:, None])
                st = {}
            if key is not None:
                key, sub = jax.random.split(key)
                nxt = sample(lg, sub)
            else:
                nxt = sample(lg)
            return (nxt, state, key), (tok, st)

        (_, _, _), (toks, sts) = jax.lax.scan(
            step, (tok, state, key), None, length=max_new_tokens)
    toks = jnp.moveaxis(toks, 0, 1)                    # (B, max_new)
    granted = used = skipped = None
    if "rows" in sts:
        used = jnp.mean(sts["rows"], axis=0)           # (B,)
        if n_planes is not None:
            granted = n_planes
            budget = n_planes.astype(jnp.float32)
        else:
            # no explicit budget: layers ran at their static default
            granted = budget = float(model.cfg.dslot.n_planes
                                     or model.cfg.dslot.n_bits)
        skipped = 1.0 - used / budget
    bounded = jnp.mean(sts["bounded"]) if "bounded" in sts else None
    result = GenerateResult(tokens=toks, n_planes=granted,
                            planes_used_mean=used, skipped_frac=skipped,
                            planes_bounded_mean=bounded,
                            steps=max_new_tokens, phase=DONE)
    if return_stats is True:
        return toks, result.stats
    if return_stats is False:
        return toks
    return result


@dataclass
class Request:
    uid: int
    prompt: np.ndarray                 # (S,) int32
    max_new: int
    n_planes: int | None = None        # per-request DSLOT precision (None =
                                       # policy-assigned or full n_bits)
    tier: str = STANDARD               # QoS tier (repro.serve.slo.TIERS)
    deadline_steps: int | None = None  # engine steps from enqueue before
                                       # timeout eviction (None = engine's
                                       # ServeConfig.default_deadline_steps)
    on_token: Callable | None = None   # streaming: called (req, token, step)
                                       # the step each token is emitted
    out: list = field(default_factory=list)
    token_steps: list = field(default_factory=list)  # engine step per token
    done: bool = False
    dslot_stats: dict | None = None    # set on finish in DSLOT mode
    result: GenerateResult | None = None  # set on finish / cancel-in-pool
    phase: str = "new"                 # pending|prefilling|decoding|done|...
    enqueue_step: int | None = None    # engine step count at try_add
    first_token_step: int | None = None  # step that emitted out[0]

    @property
    def ttft_steps(self) -> int | None:
        """Engine steps from enqueue to first emitted token."""
        if self.enqueue_step is None or self.first_token_step is None:
            return None
        return self.first_token_step - self.enqueue_step


def _dslot_calibrated(params) -> bool:
    """True iff every prepared ``DslotWeights`` in the tree carries a
    calibrated activation scale (False when none are found)."""
    found, ok = [False], [True]

    def walk(node):
        if isinstance(node, DslotWeights):
            found[0] = True
            if node.x_scale is None:
                ok[0] = False
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return found[0] and ok[0]


class ServeEngine:
    """Slot-pool continuous batching on a single jitted decode step, with
    chunked-prefill admission interleaved into the step loop and an
    optional SLO plane-shedding control loop."""

    def __init__(self, model: Model, params,
                 cfg: ServeConfig | None = None, *,
                 n_slots: int | None = None, max_len: int | None = None,
                 sample: Callable | None = None,
                 precision_policy=None,
                 serve_config: ServeConfig | None = None):
        legacy = {k: v for k, v in (("n_slots", n_slots),
                                    ("max_len", max_len),
                                    ("sample", sample),
                                    ("precision_policy", precision_policy))
                  if v is not None}
        if serve_config is not None or legacy:
            # deprecation shim: fold the accreted keywords onto a ServeConfig
            if cfg is not None:
                raise TypeError(
                    "pass either cfg=ServeConfig(...) or the legacy "
                    "keywords, not both")
            _warn_once(
                "ServeEngine.kwargs",
                "ServeEngine(model, params, n_slots=..., max_len=..., "
                "serve_config=...) is deprecated; pass a single "
                "ServeConfig: ServeEngine(model, params, ServeConfig("
                "n_slots=..., max_len=..., ...))")
            cfg = dataclasses.replace(serve_config or ServeConfig(), **legacy)
        self.cfg = cfg or ServeConfig()
        self.model = model
        self.dslot = mlp_uses_dslot(model.cfg)
        if self.cfg.mesh is not None:
            # tensor-parallel serving: the DSLOT layers shard via the mesh
            # baked into their prepared state below; the dense projections
            # pick up GSPMD constraints through the pspec registry — both
            # inside the SAME per-step jit, so one engine step still issues
            # exactly one (sharded) forward.
            from repro.models import pspec
            if any(t != AxisType.Auto for t in self.cfg.mesh.axis_types):
                raise ValueError(
                    "ServeConfig.mesh needs Auto axes (the model places "
                    "activations with sharding constraints); build it with "
                    "repro.launch.mesh.auto_mesh / make_test_mesh")
            pspec.set_mesh(self.cfg.mesh)
        # one-time weight-stationary lowering: every decode step executes
        # against cached digit-plane tables (no per-call re-encode)
        self.params = model.prepare_dslot(
            params, mesh=self.cfg.mesh,
            tp_axis=self.cfg.tp_axis) if self.dslot else params
        self.n_slots = self.cfg.n_slots
        self.max_len = self.cfg.max_len
        self.sample = self.cfg.sample or greedy_sample
        self.policy = self.cfg.precision_policy
        self.n_bits = model.cfg.dslot.n_bits
        self.calibrated = (not self.dslot) or _dslot_calibrated(self.params)
        self.slo: SloController | None = None if self.cfg.slo is None \
            else SloController(self.n_bits, self.cfg.slo)
        self.state = model.init_decode_state(self.n_slots, self.max_len)
        if self.cfg.mesh is not None:
            # weights and the KV pool live on every device of the mesh from
            # the start: left on the default device, each sharded step would
            # copy them out of device 0 again
            everywhere = NamedSharding(self.cfg.mesh, PartitionSpec())
            self.params = jax.device_put(self.params, everywhere)
            self.state = jax.device_put(self.state, everywhere)
        self.slot_req: list[Request | None] = [None] * self.n_slots
        self.next_tok = np.zeros(self.n_slots, np.int32)
        self.last_budget: np.ndarray | None = None  # budgets of last decode
        self._acc_planes = np.zeros(self.n_slots, np.float64)
        self._acc_bounded = np.zeros(self.n_slots, np.float64)
        self._acc_steps = np.zeros(self.n_slots, np.int64)
        self._acc_moe = np.zeros((self.n_slots, 2), np.float64)
        self._steps = 0
        self._ttft_obs: list[int] = []     # TTFTs landed since last signal
        self._last_rows_mean: float | None = None
        # hardening state: the fault log (step, site, repr(exc)) of every
        # absorbed exception, the quarantine/timeout eviction records, and
        # the optional deterministic fault-injection plane
        self.errors: list[tuple[int, str, str]] = []
        self.quarantined: list[tuple[int, int]] = []   # (step, uid)
        self.timeouts: list[tuple[int, int]] = []      # (step, uid)
        self.injector: FaultInjector | None = \
            None if self.cfg.faults is None else FaultInjector(self.cfg.faults)
        self._closed = False
        self._state_axes = None            # lazy: KV-corruption fault hook
        self.pipeline = PrefillPipeline(
            model=model, params=self.params, max_len=self.max_len,
            chunk=self.cfg.prefill_chunk,
            chunks_per_step=self.cfg.chunks_per_step,
            max_queue=self.cfg.max_queue,
            jit_chunks=self.cfg.jit_prefill,
            dslot=self.dslot, calibrated=self.calibrated,
            injector=self.injector)

        self._decode = decode_program(model, self.n_slots)
        # a finished lane's row into its slot of the pool, donated: written
        # in place, where an eager per-leaf copy would hold the pool twice
        self._merge = jax.jit(_lane_ops(self.pipeline._axes, jit=False)[1],
                              donate_argnums=0)

    @property
    def serve_config(self) -> ServeConfig:
        """Back-compat alias for the engine's config."""
        return self.cfg

    # ------------------------------------------------------------ requests

    def try_add(self, req: Request) -> bool:
        """Enqueue a request for admission — NON-blocking.

        No model work happens here: the request joins the FIFO admission
        queue and the step loop prefills it one ``prefill_chunk`` at a time,
        interleaved with pooled decode steps.  Returns False only when the
        admission queue is full (``ServeConfig.max_queue``) — retry later.

        Requests that can NEVER run are rejected immediately with
        ``ValueError``: an empty prompt, a non-1-D or non-integer-dtype
        prompt, token ids outside ``[0, vocab_size)`` (either would poison
        the shared embedding gather / KV ring for co-batched requests), a
        non-positive generation budget, ``len(prompt) + max_new > max_len``
        (the KV ring would wrap and silently corrupt the sequence
        mid-decode), a whole-prompt admission (``prefill_chunk == 0``)
        whose prompt exceeds the KV ring capacity (for SWA the ring is only
        ``window`` wide — a one-chunk ingest would wrap and evict its own
        in-window keys), an unknown QoS tier, or — in DSLOT mode — a
        per-request plane budget whose prompt would be split into multiple
        chunks on a model with NO calibrated activation scale (per-call-max
        quantization is not chunk-invariant, so the chunked prefill would
        silently diverge from a one-shot prefill of the same prompt; pin
        ``DslotConfig.act_scale``).

        Policy-assigned precision (DSLOT mode) is granted here, at enqueue:
        a scalar policy (``Fixed``, ``AdaptiveBudget``) grants this
        request's plane budget directly; a per-layer policy
        (``PerLayerSchedule``) is flattened to the budget of the engine's
        DSLOT consumer (the MLP up-projection, falling back to the
        schedule's ``"*"`` default).
        """
        if self._closed:
            raise RuntimeError("ServeEngine is closed")
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1:
            raise ValueError(
                f"request {req.uid}: prompt must be 1-D, got shape "
                f"{prompt.shape}")
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"request {req.uid}: prompt dtype {prompt.dtype} is not an "
                f"integer type — token ids must be integers (a float "
                f"prompt would be silently truncated into the shared ring)")
        req.prompt = prompt
        P = int(len(req.prompt))
        if P < 1:
            raise ValueError(f"request {req.uid}: empty prompt")
        vocab = int(self.model.cfg.vocab_size)
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= vocab:
            raise ValueError(
                f"request {req.uid}: token ids must be in [0, {vocab}), "
                f"got range [{lo}, {hi}] — an out-of-vocab id reads "
                f"garbage through the embedding gather and poisons the "
                f"shared decode state")
        if req.max_new < 1:
            raise ValueError(
                f"request {req.uid}: max_new must be >= 1, got {req.max_new}")
        if P + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.uid}: prompt ({P}) + max_new ({req.max_new}) "
                f"= {P + req.max_new} exceeds max_len ({self.max_len}); the "
                f"KV ring would wrap and corrupt the sequence")
        cap = cache_capacity(self.model.cfg, self.max_len)
        if self.pipeline.chunk == 0 and P > cap:
            # whole-prompt admission runs the prompt as ONE chunk; wider
            # than the ring (the SWA window, when smaller than max_len) it
            # would wrap and silently evict its own in-window keys.
            raise ValueError(
                f"request {req.uid}: whole-prompt admission "
                f"(prefill_chunk=0) cannot ingest a {P}-token prompt into "
                f"a KV ring of capacity {cap} (sliding window "
                f"{self.model.cfg.window}); the ring would wrap.  Use "
                f"chunked admission (prefill_chunk > 0)")
        known_tiers = self.slo.tiers if self.slo is not None else TIERS
        if req.tier not in known_tiers:
            raise ValueError(
                f"request {req.uid}: unknown QoS tier {req.tier!r} "
                f"(known: {sorted(known_tiers)})")
        wants_budget = req.n_planes is not None or (
            self.dslot and self.policy is not None)
        if (self.dslot and not self.calibrated and wants_budget
                and 0 < self.pipeline.chunk < P):
            raise ValueError(
                f"request {req.uid}: a per-request DSLOT plane budget with "
                f"a chunked prompt ({P} tokens > prefill_chunk="
                f"{self.pipeline.chunk}) requires a calibrated activation "
                "scale — per-call max quantization is not invariant to how "
                "the prompt is split into chunks.  Set DslotConfig.act_scale"
                " (or DslotWeights.with_scale), or use prefill_chunk=0")
        if not self.pipeline.enqueue(req):
            return False        # queue full: the policy is NOT consulted, so
                                # a later retry gets a fresh grant
        if self.dslot and req.n_planes is None and self.policy is not None:
            nxt = self.policy.next_precision()
            if isinstance(nxt, dict):
                nxt = nxt.get("mlp_up_dslot", nxt.get("*", self.n_bits))
            req.n_planes = int(nxt)
        req.enqueue_step = self._steps
        return True

    def cancel(self, uid: int) -> bool:
        """Abandon a request wherever it is in its lifecycle.

        Pending: removed from the queue.  Mid-prefill: the private chunk
        state is dropped and the reserved slot released — the pool was
        never written, so nothing needs cleaning.  Decoding: the slot is
        freed; its stale rows are invisible to other slots (per-sequence
        rings) and are replaced wholesale by the next admission's merge.

        Cancellation is terminal: ``req.done`` is set (with
        ``phase == "cancelled"`` distinguishing it from a natural finish)
        and ``req.result`` carries whatever was produced, so
        ``while not req.done`` driving loops exit.  A cancelled request
        is never returned from ``step()``.
        """
        return self._evict(uid, CANCELLED) is not None

    def _evict(self, uid: int, phase: str) -> Request | None:
        """Terminate a request wherever it lives (queue, prefill lane, or
        decode slot) with the given terminal phase, freeing its slot and
        lane, and attach its ``GenerateResult``.  The shared machinery
        behind ``cancel`` (CANCELLED), deadline eviction (TIMEOUT),
        poisoned-slot isolation (QUARANTINED) and admission-failure
        eviction (FAILED)."""
        found = next((r for r in list(self.pipeline.queue)
                      + [t.req for t in self.pipeline.active]
                      if r.uid == uid), None)
        if self.pipeline.cancel(uid):
            if found is not None:
                found.phase = phase
                found.result = self._result_of(found)
            return found
        for i, req in enumerate(self.slot_req):
            if req is not None and req.uid == uid:
                req.phase = phase
                req.done = True
                req.result = self._result_of(req)
                self.slot_req[i] = None
                return req
        return None

    def stream(self, req: Request) -> Iterator[int]:
        """Generator handle over a request's token stream.

        Admits ``req`` if it is new (raising ``RuntimeError`` on a full
        queue), then drives ``step()`` and yields each generated token as
        it lands — the pull-based twin of the ``Request.on_token`` push
        callback.  Other slots keep decoding underneath; interleave
        ``stream`` handles freely with direct ``step()`` calls.

        A consumer that stops iterating (``break``, garbage collection,
        explicit ``close()``) CANCELS the request: the ``finally`` below
        runs on ``GeneratorExit``, so an abandoned stream frees its slot
        and lane instead of stranding them forever (the pre-hardening
        leak).
        """
        if req.phase == "new" and not self.try_add(req):
            raise RuntimeError(
                f"request {req.uid}: admission queue full")
        sent = 0
        try:
            while True:
                while sent < len(req.out):
                    yield req.out[sent]
                    sent += 1
                if req.done:
                    return
                self.step()
        finally:
            if not req.done:
                self.cancel(req.uid)

    @property
    def queue_depth(self) -> int:
        """Admitted-but-not-yet-decodable requests (pending + prefilling)."""
        return len(self.pipeline)

    @property
    def steps(self) -> int:
        """Engine steps taken so far (the clock ``ttft_steps`` is in)."""
        return self._steps

    def slot_phases(self) -> list[str]:
        """Phase of each pool slot: 'free' | PREFILLING | DECODING."""
        held = {t.slot for t in self.pipeline.active}
        return [PREFILLING if i in held
                else (DECODING if r is not None else "free")
                for i, r in enumerate(self.slot_req)]

    def _free_slot(self, exclude: set = frozenset()) -> int | None:
        held = {t.slot for t in self.pipeline.active}
        for i, r in enumerate(self.slot_req):
            if r is None and i not in held and i not in exclude:
                return i
        return None

    def _budget_vector(self) -> jax.Array:
        npl = []
        for r in self.slot_req:
            base = self.n_bits if r is None or r.n_planes is None \
                else r.n_planes
            if self.slo is not None and r is not None:
                base = self.slo.budget_for(r.tier, base)
            npl.append(int(base))
        return jnp.asarray(npl, jnp.int32)

    # ------------------------------------------------------------ stepping

    def _admission_tick(self) -> None:
        """One step's worth of admission work: one batched lane forward
        advancing every active task, plus leftover ``chunks_per_step``
        budget spent on the head task (the hybrid tick); completed prefills
        are merged into their slots' rows (the PR 2 per-slot position
        vectors keep live slots undisturbed) and decode from THIS step
        on."""
        for task in self.pipeline.tick(self._free_slot):
            with TraceAnnotation("serve.merge", uid=task.req.uid):
                i = task.slot
                self.state = self._merge(self.state, task.state, i)
                self.slot_req[i] = task.req
                task.req.phase = DECODING
                self._acc_planes[i] = 0.0
                self._acc_bounded[i] = 0.0
                self._acc_steps[i] = 0
                self._acc_moe[i] = 0.0
                # first token through the engine's sample fn (greedy by
                # default), matching what ``generate`` does with its
                # prefill logits
                self.next_tok[i] = int(
                    jax.device_get(self.sample(task.logits)[0]))

    def _evict_timeouts(self) -> int:
        """Deadline sweep: evict every request past its deadline — queued,
        mid-prefill, or decoding — with ``phase == "timeout"``.  Runs
        BEFORE the admission tick so an already-overdue queued request
        never claims a lane.  Returns the eviction count (fed to the SLO
        controller as pressure)."""
        default = self.cfg.default_deadline_steps
        expired = []
        for req in (list(self.pipeline.queue)
                    + [t.req for t in self.pipeline.active]
                    + [r for r in self.slot_req if r is not None]):
            dl = req.deadline_steps if req.deadline_steps is not None \
                else default
            if dl is None or req.enqueue_step is None:
                continue
            if self._steps - req.enqueue_step > dl:
                expired.append(req.uid)
        n = 0
        for uid in expired:
            if self._evict(uid, TIMEOUT) is not None:
                self.timeouts.append((self._steps, uid))
                n += 1
        return n

    def _fault_slot(self, fault) -> int | None:
        """Resolve a fault's target to a pool slot.  ``uid`` targets wait
        (return None, keeping the fault pending) until the request is
        actually decoding; ``slot`` targets fire as planned."""
        if fault.uid is not None:
            for i, r in enumerate(self.slot_req):
                if r is not None and r.uid == fault.uid:
                    return i
            return None
        if fault.slot is not None and 0 <= fault.slot < self.n_slots:
            return fault.slot
        return None

    def _corrupt_slot(self, state, slot: int):
        """Scribble NaN over one slot's floating-point rows of the decode
        state (KV ring) — the ``kv_corrupt`` fault hook.  Int leaves (ring
        positions) are left intact, so the corruption models a bad VALUE
        write, not broken indexing; the quarantine guard catches the NaN
        logits it produces on the very next decode step."""
        if self._state_axes is None:
            self._state_axes = _batch_axes(self.model, self.max_len)

        def scribble(leaf, ax):
            if ax < 0 or not jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf
            idx = (slice(None),) * ax + (slice(slot, slot + 1),)
            return leaf.at[idx].set(jnp.nan)

        return jax.tree.map(scribble, state, self._state_axes)

    def step(self) -> list[Request]:
        """One engine step: deadline sweep, admission chunk(s), SLO
        control, then advance all live slots by one token.  Returns
        finished requests.

        NEVER raises (a closed engine excepted): exceptions from admission
        or decode work are retried up to ``ServeConfig.max_step_retries``
        times within the step and logged to ``self.errors``.  Admission
        that fails every retry evicts its in-flight tasks with
        ``phase == "failed"`` (a deterministically poisoned prompt must not
        wedge the lanes forever); a decode that fails every retry stalls
        the pool one step with state untouched — both leave the engine in a
        state where ``check_invariants()`` passes and the next ``step()``
        proceeds.

        Each phase is a ``jax.profiler`` host span (``serve.step`` holding
        ``serve.admit``, ``serve.launch``, ``serve.readback`` and
        ``serve.emit``; ``docs/serving.md``, "Observability"): recorded
        only while a profiler trace is active, on the device events' clock.
        """
        if self._closed:
            raise RuntimeError("ServeEngine is closed")
        self._steps += 1
        with StepTraceAnnotation("serve.step", step_num=self._steps):
            inj = self.injector
            if inj is not None:
                inj.begin_step(self._steps)
                for f in inj.slow_steps():        # artificial latency
                    time.sleep(f.value or 0.0)
                for uid in inj.cancels():         # replayable cancel storms
                    self.cancel(uid)
            with TraceAnnotation("serve.admit"):
                self._admit(inj)
            if all(r is None for r in self.slot_req):
                return []
            with TraceAnnotation("serve.launch"):
                budgets, decoded = self._launch(inj)
            if decoded is None:
                # decode failed every retry: state/tokens/accounting
                # untouched, the pool stalls exactly one step and retries
                # next step
                return []
            with TraceAnnotation("serve.readback"):
                nxt, fin, rows, bounded, moe = self._readback(
                    decoded, budgets, inj)
            with TraceAnnotation("serve.emit"):
                return self._emit(nxt, fin, rows, bounded, moe)

    def _admit(self, inj) -> None:
        """Deadline sweep, the admission tick (retried), SLO update."""
        timed_out = self._evict_timeouts()
        f0 = self.pipeline.forwards
        for _ in range(self.cfg.max_step_retries + 1):
            try:
                if inj is not None:
                    inj.raise_if("admission_tick")
                self._admission_tick()
                break
            except Exception as e:  # noqa: BLE001 — absorb, log, retry
                self.errors.append((self._steps, "admission", repr(e)))
        else:
            # every retry failed: fail the in-flight admissions so the
            # lanes recover next step (the queue is untouched — see the
            # step() docstring)
            for task in list(self.pipeline.active):
                self._evict(task.req.uid, FAILED)
        if self.slo is not None:
            # load signals: queue AFTER this step's admissions, the TTFTs
            # that landed since the last update, and last decode's planes
            self.slo.update(SloSignals(
                queue_depth=self.queue_depth,
                ttft_steps=self._ttft_obs,
                decode_stalled=self.pipeline.forwards > f0,
                planes_used_mean=self._last_rows_mean,
                timed_out=timed_out))
            self._ttft_obs = []

    def _launch(self, inj):
        """Budgets, the token input and the pooled decode dispatch
        (retried), committing the new state as it returns: the call
        donates the old one.  ``decoded`` (logits, aux) is None when every
        retry raised; a try that raises before the call leaves the state
        intact for the next."""
        toks = jnp.asarray(self.next_tok[:, None])
        budgets = self._budget_vector()
        for _ in range(self.cfg.max_step_retries + 1):
            try:
                if inj is not None:
                    inj.raise_if("decode_forward")
                logits, self.state, aux = self._decode(
                    self.params, self.state, toks, budgets)
                return budgets, (logits, aux)
            except Exception as e:  # noqa: BLE001
                self.errors.append((self._steps, "decode", repr(e)))
        return budgets, None

    def _readback(self, decoded, budgets, inj):
        """Fetch what the host needs: the budgets, the finite guard, the
        sampled tokens, the planes account and the expert rows (one fetch
        with the tokens)."""
        logits, aux = decoded
        self.last_budget = np.asarray(jax.device_get(budgets))
        poisoned = False
        if inj is not None:
            logits, poisoned = inj.poison_logits(logits, self._fault_slot)
        fin = None
        if self.cfg.quarantine_nonfinite:
            fin = np.asarray(jax.device_get(
                jnp.all(jnp.isfinite(logits), axis=-1) if poisoned
                else aux["finite"]))
        if inj is not None:
            for slot in inj.kv_corruptions(self._fault_slot):
                self.state = self._corrupt_slot(self.state, slot)
        nxt, moe = jax.device_get((self.sample(logits), aux.get("moe_rows")))
        nxt = np.asarray(nxt)
        rows = np.asarray(jax.device_get(aux["rows"])) \
            if "rows" in aux else None
        bounded = float(jax.device_get(aux["bounded"])) \
            if "bounded" in aux else None
        self._last_rows_mean = None if rows is None else float(rows.mean())
        return nxt, fin, rows, bounded, moe

    def _emit(self, nxt, fin, rows, bounded, moe) -> list[Request]:
        """Emit each live slot's token (quarantining poisoned slots) and
        retire finished requests."""
        finished = []
        if moe is not None:
            # every slot: a slot's account restarts when a request merges
            self._acc_moe += moe
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            if fin is not None and not fin[i]:
                # quarantine BEFORE emitting: the poisoned logits never
                # reach the stream.  Only this slot is touched — rows are
                # computationally independent (per-sequence rings, row-wise
                # MLP/norm), so survivors' tokens stay bit-identical to a
                # run that never admitted the poisoned request.
                self.quarantined.append((self._steps, req.uid))
                req.phase = QUARANTINED
                req.done = True
                req.result = self._result_of(req)
                self.slot_req[i] = None
                continue
            tok = int(self.next_tok[i])
            req.out.append(tok)
            req.token_steps.append(self._steps)
            if req.first_token_step is None:
                req.first_token_step = self._steps
                if req.ttft_steps is not None:
                    self._ttft_obs.append(req.ttft_steps)
            if req.on_token is not None:
                req.on_token(req, tok, self._steps)
            self.next_tok[i] = nxt[i]
            if rows is not None:
                self._acc_planes[i] += float(rows[i])
                if bounded is not None:
                    self._acc_bounded[i] += bounded
                self._acc_steps[i] += 1
            if len(req.out) >= req.max_new:
                req.done = True
                req.phase = DONE
                self._finish_stats(i, req)
                finished.append(req)
                self.slot_req[i] = None
        return finished

    # -------------------------------------------------------- shutdown

    @property
    def closed(self) -> bool:
        """True once :meth:`close` has sealed the engine."""
        return self._closed

    def live_requests(self) -> list[Request]:
        """Every request the engine still owes work: queued, mid-prefill,
        and decoding."""
        return (list(self.pipeline.queue)
                + [t.req for t in self.pipeline.active]
                + [r for r in self.slot_req if r is not None])

    def drain(self, max_steps: int | None = None) -> list[Request]:
        """Graceful shutdown, phase 1: step until every admitted request
        reaches a terminal state (finished, timed out, quarantined, or
        cancelled), admitting nothing new yourself.  Returns the requests
        that finished NATURALLY during the drain (evictions are on
        ``req.result`` / the engine's ``timeouts``/``quarantined`` logs).

        ``max_steps`` bounds the drain; ``None`` derives a worst-case
        sequential bound from the live work (every prompt's chunks plus its
        full generation budget) — exceeding it means the engine lost
        liveness, which IS worth raising about (``RuntimeError``), unlike
        anything inside ``step()``.
        """
        if self._closed:
            return []
        if max_steps is None:
            chunk = self.pipeline.chunk or self.max_len
            max_steps = 16 + sum(
                -(-len(r.prompt) // max(1, chunk)) + r.max_new
                for r in self.live_requests())
        finished: list[Request] = []
        for _ in range(max_steps):
            if not self.live_requests():
                return finished
            finished.extend(self.step())
        if self.live_requests():
            raise RuntimeError(
                f"drain did not converge in {max_steps} steps; still live: "
                f"{[r.uid for r in self.live_requests()]}")
        return finished

    def close(self) -> list[Request]:
        """Graceful shutdown, phase 2 (or immediate shutdown on its own):
        cancel everything still in flight — queued, prefilling, decoding —
        attaching each request's ``GenerateResult`` with whatever it
        produced, then seal the engine: ``try_add`` and ``step`` raise
        ``RuntimeError`` afterwards.  Idempotent.  Returns the requests
        cancelled by this call; ``drain()`` first for a shutdown that
        finishes in-flight work instead of cutting it."""
        if self._closed:
            return []
        cancelled = []
        for req in self.live_requests():
            if self._evict(req.uid, CANCELLED) is not None:
                cancelled.append(req)
        self._closed = True
        return cancelled

    def check_invariants(self) -> None:
        """Audit slot/queue/lane/ring accounting; raises
        ``repro.serve.health.InvariantViolation`` on corruption.  The chaos
        suites call this after every step."""
        from repro.serve.health import check_invariants
        check_invariants(self)

    def _result_of(self, req: Request, granted=None, used=None,
                   skipped=None, bounded=None,
                   expert_rows=None) -> GenerateResult:
        return GenerateResult(
            tokens=list(req.out), n_planes=granted,
            planes_used_mean=used, skipped_frac=skipped,
            planes_bounded_mean=bounded,
            ttft_steps=req.ttft_steps,
            expert_rows=expert_rows,
            steps=None if req.enqueue_step is None
            else self._steps - req.enqueue_step,
            phase=req.phase, uid=req.uid, tier=req.tier)

    def _finish_stats(self, i: int, req: Request) -> None:
        granted = used = skipped = bounded = None
        if self.dslot and self._acc_steps[i] > 0:
            granted = req.n_planes if req.n_planes is not None \
                else self.n_bits
            if self.slo is not None:
                # a tier floor may have raised the effective budget above
                # the granted one (e.g. reserved pins full precision)
                granted = max(int(granted), self.slo.floor(req.tier))
            used = self._acc_planes[i] / self._acc_steps[i]
            # skipped_frac counts every granted-but-not-executed plane:
            # activation-side early termination AND the weight-side static
            # MSR bound (which caps planes_used inside the kernel), so the
            # two savings compound here; planes_bounded_mean attributes the
            # static weight-side share on its own.
            skipped = 1.0 - float(used) / float(granted)
            bounded = self._acc_bounded[i] / self._acc_steps[i]
            fb = PolicyFeedback(n_planes=int(granted),
                                planes_used_mean=float(used),
                                skipped_frac=skipped, tier=req.tier)
            req.dslot_stats = {"n_planes": fb.n_planes,
                               "planes_used_mean": fb.planes_used_mean,
                               "skipped_frac": fb.skipped_frac,
                               "planes_bounded_mean": float(bounded)}
            if self.policy is not None:
                self.policy.observe(fb)
            if self.slo is not None:
                self.slo.observe(fb)
        # (rows routed, rows computed) of the expert layers over the decode
        # steps the slot took part in
        moe = tuple(map(float, self._acc_moe[i])) if self._acc_moe[i, 1] \
            else None
        req.result = self._result_of(req, granted=granted, used=used,
                                     skipped=skipped, bounded=bounded,
                                     expert_rows=moe)
