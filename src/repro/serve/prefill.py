"""Chunked-prefill admission pipeline: digit-pipelined overlap for serving.

The paper's core idea — start subsequent operations as soon as the first
digits arrive instead of waiting for the full result — applied at the
serving layer: instead of blocking the whole decode pool for one full-prompt
forward per admission (the old ``try_add``), admission work is cut into
fixed-size prompt chunks and the engine interleaves admission work with
every pooled decode step.  Live slots keep decoding at their usual cadence;
pending prompts trickle into their KV caches a chunk at a time and a slot
becomes decodable the very step its last chunk lands.

Like the serial-dataflow batching the paper's comparison baselines lean on
(Stripes; DSLR-CNN), throughput comes from keeping MANY serial streams in
flight at once: admission work is BATCHED.  Up to
``ServeConfig.chunks_per_step`` PREFILLING requests advance together in ONE
forward per engine step — each in its own **lane** of a persistent stacked
decode state, at its own ragged offset, padded to the fixed chunk width,
with per-lane position vectors and per-lane DSLOT plane budgets
(``Model.extend(..., lengths=...)``).

Tensor parallelism needs no pipeline-side code: the engine hands this
pipeline params whose ``DslotWeights`` already carry the serving mesh
(``ServeConfig.mesh`` -> ``Model.prepare_dslot``), so every jitted lane
forward — like every pooled decode step — runs N-sharded under the same
``shard_map``, one sharded forward per engine step
(``docs/distributed.md``).

Lifecycle of a request::

    try_add --> PENDING ----> PREFILLING ----------> DECODING --> DONE
               (queued,       (slot + lane           (in the pooled
                FIFO)          reserved; chunks       decode step)
                               accumulate into the
                               task's lane)

Chunk mechanics (batched mode): every chunk — the first included — runs
``Model.extend`` on the stacked lane state, starting from a freshly reset
lane (an empty ring at position 0 extends bit-identically to a one-shot
``Model.prefill``: masked ring entries are healed by the online softmax).
Lanes are **private** to their tasks — the pool is written exactly once, by
the engine's merge on completion, which replaces the reserved slot's rows with
the finished lane's rows.  That makes the pipeline trivially safe against
everything that happens to the pool in between (pooled decode steps write
garbage KV into reserved rows exactly as they always did into free rows;
the final merge wipes it) and makes cancelling a mid-prefill request free:
drop the task, the lane is reset when the next request claims it.

Right-padding is harmless by construction: pad rows write nothing into the
ring (``q_valid`` masks the scatter), pass through the recurrent scans as
exact identity steps, and don't advance the lane's position, so a ragged
tail chunk costs one fixed-width forward and nothing else.  EVERY zoo
stack batches: sliding-window attention extends chunk-by-chunk by carrying
the pre-write ring alongside each chunk's own keys (so ring recycling can
never evict a live in-window key — ``models/attention.py``), and the
recurrent mixers (ssm/rglru) mask their scans so pad rows carry state
through unchanged.

The tick is HYBRID: the one batched forward advances every active lane,
and any leftover ``chunks_per_step`` budget is spent on extra sequential
chunks of the HEAD task (FIFO) — a lone admission still gets
``chunks_per_step`` chunks per tick, a full lane pool gets one chunk per
lane, and anything in between degrades smoothly.  Chunk boundaries are
fixed multiples of ``chunk`` regardless of which tick runs them, so the
schedule never changes the computed tokens.

``chunk == 0`` means whole-prompt admission: each tick runs ONE eager
batched forward at the widest remaining prompt among the claimed tasks, so
every claimed task completes in the tick it was claimed (eager because
every distinct width would otherwise be a fresh full-model compile).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.models.attention import cache_capacity
from repro.runtime import precision_scope

if TYPE_CHECKING:                                    # pragma: no cover
    from repro.serve.engine import Request

__all__ = ["PENDING", "PREFILLING", "DECODING", "DONE", "CANCELLED",
           "TIMEOUT", "QUARANTINED", "FAILED",
           "PrefillTask", "PrefillPipeline"]

# Request lifecycle phases (``Request.phase``).
PENDING = "pending"          # queued, no slot yet
PREFILLING = "prefilling"    # slot reserved, prompt chunks in flight
DECODING = "decoding"        # merged into the pool, advancing every step
DONE = "done"                # finished, slot released
CANCELLED = "cancelled"      # abandoned at any earlier phase
# Terminal eviction phases (engine hardening — ``docs/serving.md``):
TIMEOUT = "timeout"          # deadline expired before finish; evicted
QUARANTINED = "quarantined"  # non-finite logits detected; slot isolated
FAILED = "failed"            # admission work kept raising past the retry
                             # budget; evicted so the lane can recover


@dataclass
class PrefillTask:
    """One in-flight admission: a request, its reserved pool slot, and the
    lane of the pipeline's stacked state its prompt chunks accumulate
    into."""
    req: "Request"
    slot: int
    lane: int = -1                   # row of the stacked lane state
    offset: int = 0                  # prompt tokens already processed
    state: dict | None = None        # the extracted lane row, on completion
    logits: Any = None               # last chunk's final-position logits
    chunks_done: int = 0

    @property
    def remaining(self) -> int:
        return len(self.req.prompt) - self.offset


def _batch_axes(model, max_len: int):
    """Locate the batch axis of every decode-state leaf (shape-only, via
    ``eval_shape`` — nothing is allocated).  -1 marks a leaf with no batch
    axis (shared across sequences)."""
    s1 = jax.eval_shape(lambda: model.init_decode_state(1, max_len))
    s2 = jax.eval_shape(lambda: model.init_decode_state(2, max_len))

    def ax(a, b):
        diffs = [i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                 if x != y]
        return diffs[0] if diffs else -1

    return jax.tree.map(ax, s1, s2)


def _lane_ops(axes, jit: bool):
    """Row extract/insert over a stacked decode state, with the lane index
    as a TRACED scalar (one compile each, any lane) — the eager per-leaf
    form costs dozens of dispatches and a full state copy per call, which
    would eat the batching win at claim/completion time."""

    def extract(state, i):
        return jax.tree.map(
            lambda leaf, a: leaf if a < 0
            else jax.lax.dynamic_slice_in_dim(leaf, i, 1, axis=a),
            state, axes)

    def insert(state, row, i):
        return jax.tree.map(
            lambda leaf, a, r: leaf if a < 0
            else jax.lax.dynamic_update_slice_in_dim(leaf, r, i, axis=a),
            state, axes, row)

    if jit:
        extract, insert = jax.jit(extract), jax.jit(insert)
    return extract, insert


@dataclass
class PrefillPipeline:
    """FIFO admission queue + the chunk executor.

    The engine calls :meth:`tick` once per step with a free-slot provider;
    the pipeline claims queue heads into slots (and lanes) as they become
    available and advances every in-flight task by one chunk in ONE batched
    forward (``chunks_per_step`` lanes), spending any leftover budget on
    extra sequential chunks of the head task (the hybrid tick) — returning
    completed tasks for the engine to merge into the pool.
    """
    model: Any
    params: Any
    max_len: int
    chunk: int = 32
    chunks_per_step: int = 1
    max_queue: int | None = None
    jit_chunks: bool = True
    dslot: bool = False          # model runs the digit-serial MLP path
    calibrated: bool = True      # prepared weights carry an act scale
    queue: deque = field(default_factory=deque)
    active: list = field(default_factory=list)   # in-flight PrefillTasks
    forwards: int = 0                            # model forwards run (a
                                                 # batched tick counts 1)
    injector: Any = None         # repro.serve.faults.FaultInjector — the
                                 # engine installs its own; consulted just
                                 # before every lane forward

    def __post_init__(self):
        cap = cache_capacity(self.model.cfg, self.max_len)
        if self.chunk > cap:
            # batched chunks are padded to the FULL chunk width; wider than
            # the KV ring (max_len, or the SWA window when smaller), the
            # pad phantoms would alias real slots (the attention layer
            # rejects such chunks).  Clamping loses nothing: for full
            # attention a prompt can never exceed max_len anyway (try_add
            # validates), and for SWA any chunk width <= window is exact.
            self.chunk = cap
        model, max_len = self.model, self.max_len
        # Lane-pool batched admission: one persistent stacked decode state
        # with `chunks_per_step` lanes; every tick advances every active
        # lane by one fixed-width chunk in a single forward.  Tokens are
        # always padded to (lanes, chunk), lengths carry the ragged tails,
        # and the per-lane DSLOT budgets enter as a traced (lanes,) i32
        # vector — so there is exactly ONE compile, total, shared by every
        # admission at every precision and every ragged tail length.
        # (``chunk == 0`` is whole-prompt admission: widths vary per tick,
        # so the forward stays eager — each distinct width would otherwise
        # be a fresh full-model compile.)
        self.lanes = max(1, self.chunks_per_step)
        self._axes = _batch_axes(model, max_len)
        self._lane_state = model.init_decode_state(self.lanes, max_len)
        self._fresh = model.init_decode_state(1, max_len)
        self._extract_lane, self._insert_lane = _lane_ops(
            self._axes, self.jit_chunks)

        def _extend_lanes(params, state, tokens, lengths, npl):
            with precision_scope(npl):
                return model.extend(params, state, tokens,
                                    lengths=lengths)

        if self.jit_chunks and self.chunk > 0:
            _extend_lanes = jax.jit(_extend_lanes)
        self._extend_lanes = _extend_lanes

    def _resolve_precision(self, req: "Request | None") -> int:
        """The request's plane budget as a python int.

        ``None`` (no request, or no explicit budget) resolves HERE (at
        python level) to what ``scope(None)`` would have meant eagerly —
        fall through to the layer default (``cfg.dslot.n_planes``, then
        ``n_bits``).  Passing None into the traced scope instead would be
        wrong twice over: it is untraceable, and a traced ``n_bits``
        stand-in would override a layer default smaller than ``n_bits``.
        """
        d = self.model.cfg.dslot
        if req is not None and req.n_planes is not None:
            return int(req.n_planes)
        return int(d.n_planes or d.n_bits)

    # ------------------------------------------------------------- queue

    def __len__(self) -> int:
        """Admissions not yet decodable: queued + in-flight."""
        return len(self.queue) + len(self.active)

    def enqueue(self, req: "Request") -> bool:
        if self.max_queue is not None and len(self) >= self.max_queue:
            return False
        if (self.dslot and not self.calibrated
                and req.n_planes is not None
                and 0 < self.chunk < len(req.prompt)):
            # Chunked prefill quantizes each chunk's activations separately;
            # without a calibrated scale the per-call max fallback makes the
            # result depend on WHERE the prompt was split — a budgeted
            # admission would silently diverge from a one-shot prefill of
            # the same prompt.  Refuse instead of drifting.
            raise ValueError(
                f"request {req.uid}: a per-request DSLOT plane budget with "
                f"a chunked prompt ({len(req.prompt)} tokens > prefill_"
                f"chunk={self.chunk}) requires a calibrated activation "
                "scale — per-call max quantization is not chunk-invariant. "
                "Set DslotConfig.act_scale (or DslotWeights.with_scale), "
                "or use prefill_chunk=0")
        req.phase = PENDING
        self.queue.append(req)
        return True

    def cancel(self, uid: int) -> bool:
        """Drop a pending or in-flight admission.  Mid-prefill cancellation
        is free: the pool was never written, so only the task is discarded —
        its reserved slot is released, and its lane is reset when the next
        claimed request reuses it.  Co-batched survivors are untouched
        (lanes are independent batch rows).  A cancelled request is
        terminal: ``done`` is set so completion loops exit."""
        for req in self.queue:
            if req.uid == uid:
                self.queue.remove(req)
                req.phase = CANCELLED
                req.done = True
                return True
        for task in self.active:
            if task.req.uid == uid:
                task.req.phase = CANCELLED
                task.req.done = True
                self.active.remove(task)
                return True
        return False

    # ------------------------------------------------------------- stepping

    def tick(self, free_slot: Callable[[set], int | None]
             ) -> list[PrefillTask]:
        """Run one step's worth of admission work.

        ``free_slot(exclude)`` returns a claimable slot index not in
        ``exclude``, or None (pool full).  Returns the tasks whose LAST
        chunk landed this tick — the engine merges them and their slots
        decode this same step.  Claiming happens only at tick start,
        before any chunk lands, so admission can never double-book a
        slot completed within the tick.

        HYBRID schedule: claim queue heads into free (slot, lane) pairs up
        to ``chunks_per_step`` lanes, advance ALL active tasks by one chunk
        in a single stacked forward, then spend any leftover
        ``chunks_per_step`` budget on extra sequential chunks of the HEAD
        task (FIFO).  Chunk boundaries are fixed multiples of ``chunk``
        regardless of which tick runs them, so the hybrid schedule never
        changes the computed tokens — only how soon they land.
        """
        completed: list[PrefillTask] = []
        while self.queue and len(self.active) < self.lanes:
            slot = free_slot(set())
            if slot is None:
                break
            req = self.queue.popleft()
            req.phase = PREFILLING
            lane = min(set(range(self.lanes))
                       - {t.lane for t in self.active})
            # reset the lane: an empty ring at position 0 (a previous
            # occupant's stale keys would otherwise be causally visible)
            self._lane_state = self._insert_lane(self._lane_state,
                                                 self._fresh, lane)
            self.active.append(PrefillTask(req=req, slot=slot, lane=lane))
        budget = max(1, self.chunks_per_step)
        spent = 0
        while spent < budget and self.active:
            targets = list(self.active) if spent == 0 else [self.active[0]]
            completed.extend(self._forward_lanes(targets))
            spent += len(targets)
        return completed

    def _forward_lanes(self, targets: list[PrefillTask]
                       ) -> list[PrefillTask]:
        """Advance ``targets`` by one chunk in ONE stacked forward; returns
        the tasks whose prompt is now fully in (extracted from their
        lanes).  Non-target lanes ride along with zero-length rows —
        ``q_valid`` masking makes them exact no-ops on the lane state.
        Building the inputs and dispatching the forward is the
        ``serve.prefill`` host span, tagged with the targets' uids."""
        with TraceAnnotation("serve.prefill", uids=",".join(
                str(t.req.uid) for t in targets)):
            L = self.lanes
            c = self.chunk if self.chunk > 0 \
                else max(t.remaining for t in targets)
            toks = np.zeros((L, c), np.int32)
            lens = np.zeros((L,), np.int32)
            npl = np.full((L,), self._resolve_precision(None), np.int32)
            for t in targets:
                end = min(t.offset + c, len(t.req.prompt))
                n = end - t.offset
                toks[t.lane, :n] = t.req.prompt[t.offset:end]
                lens[t.lane] = n
                npl[t.lane] = self._resolve_precision(t.req)
            if self.injector is not None:
                # fault hook: a raise here leaves the tick transactional — no
                # task offset moved, the lane state untouched (the forward is a
                # functional update), so the engine's retry re-runs this exact
                # chunk against this exact state.
                self.injector.raise_if("lane_forward")
            logits, self._lane_state = self._extend_lanes(
                self.params, self._lane_state, jnp.asarray(toks),
                jnp.asarray(lens), jnp.asarray(npl))
        self.forwards += 1
        completed: list[PrefillTask] = []
        for t in targets:
            t.offset += int(lens[t.lane])
            t.chunks_done += 1
            if t.offset >= len(t.req.prompt):
                t.logits = logits[t.lane:t.lane + 1]
                t.state = self._extract_lane(self._lane_state, t.lane)
                self.active.remove(t)
                completed.append(t)
        return completed
