"""Bi-metric serving engine: the paper's deployment story, end to end.

* the **cheap tower** (e.g. qwen3-0.6b / bge-micro-like) runs locally and
  embeds the corpus once at index-build time — the graph index is built on
  those embeddings only (Theorem 1.1 property 1);
* the **expensive tower** (e.g. deepseek-v3 / SFR-Mistral-like) is the
  ground-truth metric D: scoring a document costs a forward pass. The engine
  enforces the call budget *exactly* — the quota is literally a compute
  budget on the big model;
* queries run the two-stage search **as a batch**. Stage 1 is one
  batched-engine run under d on device. Stage 2 drives the *same* core hot
  loop (``repro.core.beam.plan_step`` / ``commit_scores``) from the host:
  each wave is planned on device for every query at once, the union of
  documents the wave needs is drained through the expensive tower in batched
  forward passes, and the scores are committed back on device. Per-query
  accounting is identical to running each query alone (a document counts
  against a query's quota the first time that query scores it), while the
  tower only ever embeds a document once per engine lifetime — the
  cross-query cache is pure compute savings.

The native request unit is a frozen :class:`SearchRequest` (tokens, quota,
k, n_seeds, expand_width, deadline_ms, priority); results are
:class:`SearchResult` (ids, D-dists, :class:`ServeStats`). Two drives:

* **synchronous** — :meth:`BiMetricEngine.query_batch` /
  :meth:`BiMetricEngine.query` run one request batch to completion inline;
* **asynchronous** — :meth:`BiMetricEngine.submit` hands one request to a
  deadline/priority-ordered admission queue and returns a
  :class:`ServeFuture`. The engine keeps one resident **slot pool**: an
  (S,)-row :class:`repro.core.beam.BatchedSearchState` (sharded through a
  :class:`repro.core.beam.ShardedStepper` when ``shards > 1``) whose rows
  are recycled continuously. A finished query frees its slot *mid-flight* —
  its future resolves the step it goes inactive, not at a wave boundary —
  and admission refills freed rows from the queue on every plan/commit
  step (``repro.core.beam.reset_slots``), so a long-running request never
  blocks its neighbors (no head-of-line blocking, the continuous-batching
  idiom). The drive thread overlaps the expensive-tower drain of the
  current step with the cheap-tower embed + stage-1 search of the next
  admission group; per-slot drains replace the retired per-wave ping-pong.

Because every budget knob (quota, beam width, step cap, seeds, expand
width) is a per-row operand in the core engine and the pools are streaming
exact top-P structures, a slot row's trajectory is bit-exact to running the
same request through the synchronous drive — admission order, slot-mates
and pool-capacity growth are all invisible to a request's answer.

``EmbedTower`` wraps (params, config, pooling); swap in any LM arch config.
"""
from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import functools
import heapq
import math
import queue
import threading
import time
import warnings
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import kernels
from repro.core import beam, covertree, vamana
from repro.distributed import sharding
from repro.kernels import ops
from repro.models import transformer as T
from repro.serve import faults as serve_faults

Array = jax.Array


class DeadlineExceeded(Exception):
    """A request's ``deadline_ms`` expired before it resolved.

    Raised into the request's future by the admission layer (expiry while
    queued) or, under ``on_tower_failure="fail"``, by the drive loop's
    mid-flight enforcement (expiry while resident in a slot — checked on
    every step *and* while a tower drain is in flight, so a hung drain
    cannot stall it). Under ``on_tower_failure="degrade"`` a mid-flight
    expiry resolves the request with proxy-ranked results
    (``ServeStats.degraded``) instead. Every expiry is counted in
    ``EngineCounters.deadline_misses``."""


class TowerFailure(RuntimeError):
    """The expensive-tower lane gave up on a request.

    Raised into affected futures under ``on_tower_failure="fail"`` when
    the lane's bounded retries are exhausted, a failure is non-retryable,
    the drain timed out, or the circuit breaker is open. ``__cause__``
    carries the original tower exception with its traceback. Only the
    affected requests fail — the engine keeps serving."""


class TowerTimeout(TowerFailure):
    """A tower-lane call exceeded ``drain_timeout_ms`` (hung lane).

    Never retried inline (the lane is serial — a retry would queue behind
    the hung call); the breaker records the failure and the
    ``on_tower_failure`` policy resolves the resident requests."""


class AdmissionFailed(RuntimeError):
    """A request's admission group failed before slot residency.

    A cheap-tower embed or stage-1 error fails only that group's futures
    (``__cause__`` carries the original exception); resident slots and
    later admissions are untouched."""


class EngineFailure(RuntimeError):
    """Last resort: an unexpected drive-loop error that may have poisoned
    the resident device state. Every resident/staged future fails with
    this (``__cause__`` carries the original traceback) and the state is
    dropped; the engine itself keeps serving — the next admission
    re-initializes a fresh resident state. Tower failures never take this
    path (they have isolation paths: retry, breaker, policy)."""


# --------------------------------------------------------------------------
# legacy-form deprecation shims (the PR-5 ``backend=`` pattern: warn once
# per (call-site, form), keep the old behavior exactly)
# --------------------------------------------------------------------------
_warned: set[tuple[str, str]] = set()


def _warn_legacy(func: str, form: str) -> None:
    key = (func, form)
    if key in _warned:
        return
    _warned.add(key)
    warnings.warn(
        f"{func}: the legacy {form} call form is deprecated; pass a "
        "repro.serve.SearchRequest instead",
        DeprecationWarning, stacklevel=3)


@dataclasses.dataclass(frozen=True, eq=False)
class SearchRequest:
    """One search request — the native unit of the serve API.

    ``tokens`` is the (S,) query token row; ``quota`` the exact expensive-D
    call budget; ``k`` the result size; ``n_seeds`` the stage-1 seed count
    (None = the ``max(1, quota // 2)`` default); ``expand_width`` the
    stage-2 frontier width (per-request — slot-mates may differ);
    ``deadline_ms`` a queue deadline relative to submit (expiry while
    *queued* fails the future with :class:`DeadlineExceeded`); ``priority``
    orders admission (higher first, FIFO within a priority).
    """

    tokens: np.ndarray
    quota: int
    k: int = 10
    n_seeds: int | None = None
    expand_width: int = 1
    deadline_ms: float | None = None
    priority: int = 0


@dataclasses.dataclass
class ServeStats:
    d_calls: int = 0
    D_calls: int = 0  # expensive-tower document scorings (the budget)
    # async slot drive only: submit -> slot-admission wait, and admission ->
    # future-resolution compute. Both 0.0 on the synchronous drives, which
    # have no queueing to measure.
    queue_ms: float = 0.0
    compute_ms: float = 0.0
    # admission-time snapshots (async slot drive only)
    slot_occupancy: int = 0
    queue_depth: int = 0
    # True when the graceful-degradation path resolved this request (tower
    # open-circuit, tower-down policy, or mid-flight deadline expiry under
    # on_tower_failure="degrade"): ids/dists are the stage-1 proxy ranking
    # — distances under the cheap metric d, quality bounded by the paper's
    # C-approximation factor — or, for covertree (no proxy stage), the
    # already-D-scored pool prefix. D_calls still counts scorings spent
    # before degradation.
    degraded: bool = False

    @property
    def latency_ms(self) -> float:
        """Submit -> resolve wall clock (``queue_ms + compute_ms``)."""
        return self.queue_ms + self.compute_ms


class SearchResult(NamedTuple):
    """(ids, D-dists, stats) — tuple-unpacks like the legacy return."""

    ids: np.ndarray
    dists: np.ndarray
    stats: ServeStats


@dataclasses.dataclass
class EngineCounters:
    """Cumulative serving observability (:meth:`BiMetricEngine.counters`)."""

    submitted: int = 0
    admitted: int = 0
    completed: int = 0
    cancelled: int = 0
    deadline_misses: int = 0
    queue_depth: int = 0
    slot_occupancy: int = 0
    # fault-tolerance layer (see repro.serve "Failure semantics")
    retries: int = 0  # tower-lane retry attempts after transient failures
    tower_failures: int = 0  # failed tower-lane calls (counted pre-retry)
    degraded: int = 0  # requests resolved degraded (ServeStats.degraded)
    shed: int = 0  # requests failed fast by tower-down policy "fail"
    breaker_opens: int = 0  # breaker closed->open transitions (snapshot)
    # stage-2 waves of the slot drive (``serve.wave`` spans: steps and
    # admission entry waves) and the document ids they charged
    # (``safe[keep]``, so Σ ``ServeStats.D_calls`` of the served requests)
    waves: int = 0
    doc_lookups: int = 0
    # expensive-tower drains, both drives: uncached documents embedded and
    # the forward batches they took
    drained_rows: int = 0
    drain_batches: int = 0
    # rows each tower's program computed (padding included) and those that
    # held a token, per chunk of ``EmbedTower.embed``; read in from the
    # towers (a tower shared by engines counts for all of them; a tower
    # that is not an EmbedTower leaves them 0)
    cheap_rows: int = 0
    cheap_rows_useful: int = 0
    expensive_rows: int = 0
    expensive_rows_useful: int = 0
    # traces of the stage-1 program this engine's calls set off (one per
    # (batch, pool size) bucket that no engine in the process traced first)
    stage1_traces: int = 0
    # per ``serve.*`` span name: spans closed, and their host seconds
    span_n: dict = dataclasses.field(default_factory=dict)
    span_s: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class EmbedTower:
    params: dict
    cfg: T.TransformerConfig
    # rows the program computed, and those holding a token (see row_counts)
    rows: int = dataclasses.field(default=0, init=False, compare=False)
    rows_useful: int = dataclasses.field(default=0, init=False, compare=False)
    _rows_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, init=False, repr=False, compare=False)

    def __post_init__(self):
        def program(p, toks):
            return T.embed_pool(p, toks, self.cfg)

        # the device trace names the program jit_<name>: one per tower shape
        program.__name__ = program.__qualname__ = (
            f"embed_pool_d{self.cfg.d_model}_l{self.cfg.n_layers}")
        self._embed = jax.jit(program)

    def embed(self, tokens: np.ndarray, batch: int = 64) -> np.ndarray:
        out = []
        n = tokens.shape[0]
        pad = (-n) % batch
        toks = np.pad(tokens, ((0, pad), (0, 0))) if pad else tokens
        for s in range(0, len(toks), batch):
            chunk = toks[s:s + batch]
            useful = int(np.count_nonzero(chunk.any(axis=1)))
            out.append(np.asarray(self._embed(self.params, chunk)))
            with self._rows_lock:
                self.rows += chunk.shape[0]
                self.rows_useful += useful
        return np.concatenate(out)[:n]

    def row_counts(self) -> tuple[int, int]:
        """(rows computed, rows holding a token): padding to the batch and
        all-zero empty slots are computed but hold none."""
        with self._rows_lock:
            return self.rows, self.rows_useful


class ServeFuture(concurrent.futures.Future):
    """Result handle for one :meth:`BiMetricEngine.submit` request.

    A stdlib :class:`concurrent.futures.Future`; ``result(timeout)`` blocks
    for a :class:`SearchResult`. The engine resolves exactly once; a
    user-side ``cancel()`` race is swallowed (an admitted slot still
    computes — admission has no preemption). Requests still queued when
    :meth:`BiMetricEngine.close` runs are cancelled (``result()`` raises
    ``CancelledError``); a queued deadline expiry raises
    :class:`DeadlineExceeded`."""

    def _resolve(self, value) -> None:
        try:
            self.set_result(value)
        except concurrent.futures.InvalidStateError:
            pass  # cancelled by the caller; the computed slot is discarded

    def _fail(self, exc: BaseException) -> None:
        try:
            self.set_exception(exc)
        except concurrent.futures.InvalidStateError:
            pass


@dataclasses.dataclass
class _Pending:
    """One queued request: (request, future, submit stamp)."""

    req: SearchRequest
    future: ServeFuture
    t_submit: float


@dataclasses.dataclass
class _Active:
    """Per-slot bookkeeping for an admitted request."""

    pend: _Pending
    t_admit: float
    d_calls: int
    occ_snap: int
    depth_snap: int
    # stage-1 proxy pool row (ids sorted by d-dist; vamana only) — the
    # degraded-resolution answer when the tower lane is down or the
    # deadline expires mid-flight
    proxy_ids: np.ndarray | None = None
    proxy_dists: np.ndarray | None = None


@dataclasses.dataclass
class _Prepared:
    """An admission group after tower embed + stage 1, ready to reset slots."""

    valid: list  # [(pending, slot)]
    seeds: np.ndarray  # (S, seed_cap)
    quota: np.ndarray  # (S,) — admitted rows only; 0 elsewhere
    nseed: np.ndarray  # (S,)
    d_calls: np.ndarray  # (S,)
    q_D: np.ndarray  # (S, dim_D)
    # full stage-1 pools (vamana; None for covertree) — per-slot degraded
    # answers keep the whole proxy ranking, not just the seed prefix
    proxy_ids: np.ndarray | None = None  # (S, P1)
    proxy_dists: np.ndarray | None = None  # (S, P1)


_STOP = object()  # tower-queue sentinel
_METRIC_D = "l2"  # the cheap metric d over the cheap tower's embeddings


# ---------------------------------------------------------------------------
# jitted device-lane steps (shards == 1), stage 1 (_stage1_j) among them.
# beam_width / max_steps / quota / expand_width ride as (B,) operands so
# mixed per-query budgets do not retrace; only the static caps (pool_size,
# expand_cap) recompile. The corpus and the graph are operands, never
# constants baked into a program, so engines over same-shaped corpora share
# one executable per shape.
# ---------------------------------------------------------------------------
_stage1_caller = threading.local()  # .engine: whose call is tracing


@functools.partial(jax.jit, static_argnames=(
    "n_points", "pool_size", "metric", "backend"))
def _stage1_j(corpus, adjacency, q_d, entries, width, max_steps, *,
              n_points, pool_size, metric, backend):
    """Stage 1 as one program: the cheap-metric batched greedy search over
    ``corpus`` (a :class:`repro.kernels.CorpusView`, or the raw embeddings
    on the ``ref`` route) and ``adjacency``, scored by the same
    ``ops.gather_score`` as :func:`repro.core.beam.fused_dist_fn`.
    ``width`` / ``max_steps`` are (B,) int32; every caller passes
    ``pool_size >= max(width)``. This body runs only while JAX traces it,
    so it counts the trace (``EngineCounters.stage1_traces``) on the engine
    whose call set it off."""
    eng = getattr(_stage1_caller, "engine", None)
    if eng is not None:
        with eng._mu:
            eng._counters.stage1_traces += 1
    return beam.batched_greedy_search(
        beam.fused_dist_fn(corpus, metric, backend=backend), adjacency,
        q_d, entries, n_points=n_points, beam_width=width,
        pool_size=pool_size, max_steps=max_steps, backend=backend)


@functools.partial(jax.jit, static_argnames=(
    "n_points", "pool_size", "dedup", "set_capacity"))
def _init_j(entry_ids, quota, *, n_points, pool_size, dedup, set_capacity):
    return beam.init_state(
        entry_ids, n_points=n_points, pool_size=pool_size, quota=quota,
        dedup=dedup, set_capacity=set_capacity)


def _round_capacity(quota_max: int) -> int:
    """Static sorted-set capacity for a wave: max quota rounded up to the
    next power of two, so heterogeneous request quotas fall into log-many
    capacity buckets (bounded retraces) instead of one trace per distinct
    quota. An all-quota-0 wave (admission padding only) gets a genuine
    zero-capacity set — same program shape family, no bitmap fallback."""
    return 0 if quota_max <= 0 else 1 << (int(quota_max) - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("expand_cap",))
def _plan_step_j(state, adjacency, quota, beam_width, max_steps,
                 expand_width, *, expand_cap):
    return beam.plan_step(
        state, adjacency, beam_width=beam_width, quota=quota,
        max_steps=max_steps, expand_width=expand_width,
        expand_cap=expand_cap)


_admit_j = jax.jit(beam.reset_slots)
_reopen_j = jax.jit(beam.reset_expanded)
_frontier_j = jax.jit(ops.frontier_count)


@functools.partial(jax.jit, static_argnames=("expand_cap",))
def _plan_ct_j(state, children, level, quota, beam_width, max_steps,
               expand_width, *, expand_cap):
    """Cover-tree wave plan: level-indexed child table, dedup-free lanes
    (child slabs partition each level, so a wave never repeats an id)."""
    return beam.plan_step(
        state, children, beam_width=beam_width, quota=quota,
        max_steps=max_steps, expand_width=expand_width,
        expand_cap=expand_cap, level=level, wave_dedup=False)


@jax.jit
def _wave_dists_j(doc_embs, q_D):
    """L2 under D from gathered doc embeddings (masked lanes fixed later)."""
    diff = doc_embs.astype(jnp.float32) - q_D[:, None, :].astype(jnp.float32)
    return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


# Backend is a frozen (hashable) dataclass — a jit static, so each merge
# route compiles its own program instead of tracing the knob.
_commit_j = functools.partial(
    jax.jit, static_argnames=("backend",))(beam.commit_scores)


@jax.jit
def _active_any_j(state, quota, beam_width, max_steps):
    return beam.active_mask(
        state, beam_width=beam_width, quota=quota, max_steps=max_steps).any()


@jax.jit
def _active_j(state, quota, beam_width, max_steps):
    return beam.active_mask(
        state, beam_width=beam_width, quota=quota, max_steps=max_steps)


class _SlotPool:
    """The drive thread's resident slot state (one per started engine).

    Owns the (S,)-row search state, the per-slot host vectors (quota, beam
    width, step cap, k, expand width), the resident expensive query
    embeddings, and the static-shape caps (pool size P, sorted-set capacity
    C, seed/expand lane caps). Caps only grow, in power-of-two buckets, so
    mixed workloads retrace log-many times; growth is an exact semantic
    no-op (``repro.core.beam.grow_state``). All methods run on the drive
    thread only.

    Spans (:meth:`BiMetricEngine._span`), each also counted in
    ``EngineCounters.span_n`` / ``span_s``; each opens and closes around
    code already there and adds no host sync of its own:

    * ``serve.admit`` (``group``, ``requests``) — :meth:`prepare`, on its
      own or inside a wave's drain; in it ``serve.cheap_embed``,
      ``serve.stage1`` (through its host reads of the pools) and
      ``serve.query_wait`` (the expensive query embeds), each ``group``;
    * ``serve.wave`` (``wave``, ``entry``) — one :meth:`step` /
      :meth:`step_ct`, or the entry wave of :meth:`admit` (``entry=1``);
      in it ``serve.plan`` (the plan or ``reset_slots`` dispatch through
      the host read of its ids), ``serve.drain_wait``, ``serve.gather``
      and ``serve.commit``, each ``wave``. The commit runs on the device
      after its span closes: the host next waits for it at
      :meth:`resolve_finished`'s active-mask read, or else, the device
      running its programs in order, in the next ``serve.plan``;
    * ``serve.resolve`` (``resolved``) — :meth:`resolve_finished` when
      rows finish.

    The tower lane's spans carry the id of the wave or group that asked
    for them: ``serve.tower.drain`` (``wave``; ``rows``, the wave's
    document lookups, cached ones included) and
    ``serve.tower.query_embed`` (``group``).
    """

    def __init__(self, eng: "BiMetricEngine"):
        self.eng = eng
        s = eng.slots
        self.S = s
        self.occupied = np.zeros(s, bool)
        self.active_req: list[_Active | None] = [None] * s
        self.quota = np.zeros(s, np.int32)
        self.L = np.ones(s, np.int32)
        self.ms = np.zeros(s, np.int32)
        self.k = np.ones(s, np.int32)
        self.ew = np.ones(s, np.int32)
        self.ct_level = np.zeros(s, np.int32)  # covertree descent position
        self.q_D: np.ndarray | None = None
        self.state = None
        self.pool_size = 0
        self.dedup: str | None = None
        self.cap: int | None = None
        self.ew_cap = 1
        self.groups = 0  # admission groups staged (the span id)
        self.prepared: _Prepared | None = None
        # rows whose future already resolved early (mid-flight deadline /
        # degradation while a wave was in flight): freed only at the next
        # sweep point so an in-flight commit never races a re-admission
        self.early = np.zeros(s, bool)
        self._tower_exc: BaseException | None = None

    # ---------------------------------------------------------------- admit
    def prepare(self, group: list[_Pending]) -> _Prepared | None:
        """Stage a group for admission: expensive query embeds through the
        tower lane, cheap embed + stage-1 seed search on the drive thread
        (the two overlap when the tower is already busy draining a step).
        Malformed requests fail their own future here and are dropped.

        The group is one isolation domain: a cheap-tower or stage-1 error
        fails only this group's futures (:class:`AdmissionFailed`, the
        original exception on ``__cause__``) and the engine keeps serving.
        An expensive-tower query-embed failure follows the engine's
        ``on_tower_failure`` policy — ``"degrade"`` resolves the group
        proxy-only (stage-1 ranking, ``ServeStats.degraded``) since that
        path needs no expensive embeddings at all. While the tower lane is
        open-circuit under ``"degrade"``, the group short-circuits to
        proxy-only serving without ever occupying a slot."""
        self.groups += 1
        with self.eng._span("serve.admit", group=self.groups,
                            requests=len(group)):
            try:
                return self._prepare_inner(group, self.groups)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as exc:
                tower = isinstance(exc, TowerFailure)
                shed = 0
                for pend in group:
                    if pend.future.done():
                        continue  # failed individually (malformed tokens)
                    if tower:
                        # the lane (not the group) is the failure: keep the
                        # class so callers can tell outage from bad input
                        err = TowerFailure(
                            "expensive-tower lane unavailable at admission "
                            "(see __cause__)")
                    else:
                        err = AdmissionFailed(
                            "admission group failed before slot residency "
                            "(see __cause__)")
                    err.__cause__ = exc
                    pend.future._fail(err)
                    shed += 1
                with self.eng._mu:
                    self.eng._counters.shed += shed
                return None

    def _prepare_inner(self, group: list[_Pending],
                       gid: int) -> _Prepared | None:
        eng = self.eng
        seq = eng.corpus_tokens.shape[1]
        slots = np.nonzero(~self.occupied)[0][:len(group)]
        tokens = np.zeros((self.S, seq), eng.corpus_tokens.dtype)
        quota_g = np.zeros(self.S, np.int32)
        nseed_g = np.ones(self.S, np.int32)
        valid: list = []
        for pend, slot in zip(group, slots):
            t = np.asarray(pend.req.tokens)
            if t.ndim != 1 or t.shape[0] != seq:
                pend.future._fail(ValueError(
                    f"request tokens shape {t.shape} != ({seq},)"))
                continue
            q = int(pend.req.quota)
            tokens[slot] = t
            quota_g[slot] = q
            ns = pend.req.n_seeds
            nseed_g[slot] = max(1, q // 2) if ns is None else max(1, int(ns))
            valid.append((pend, int(slot)))
        if not valid:
            return None
        blocked = eng._breaker.blocked()
        if eng.index_kind == "covertree":
            # no proxy stage 1: Algorithm 3 descends from the top cover
            # under D directly — the cheap metric's job ended at build
            # time. With the lane open-circuit there is no proxy ranking
            # to degrade to either, so the group is shed fast.
            if blocked:
                raise TowerFailure(
                    "expensive-tower lane is open-circuit and the "
                    "covertree index has no proxy stage to degrade to")
            item = ("embed_queries", tokens, gid)
            qfut = eng._tower_submit(item)
            root = np.asarray(eng._flat.root_ids, np.int32)
            seeds = np.full((self.S, root.shape[0]), -1, np.int32)
            for _, slot in valid:
                seeds[slot] = root
            with eng._span("serve.query_wait", group=gid):
                q_D = np.asarray(eng._tower_result(qfut, item, pool=self))
            return _Prepared(
                valid=valid, seeds=seeds, quota=quota_g, nseed=nseed_g,
                d_calls=np.zeros(self.S, np.int32), q_D=q_D)
        if blocked and eng.on_tower_failure == "fail":
            raise TowerFailure(
                "expensive-tower lane is open-circuit "
                f"({eng._breaker.failures} consecutive failures)")
        degrade_only = blocked  # policy "degrade": proxy-only admission
        # expensive query embed rides the tower lane; the cheap embed and
        # stage-1 proxy search run here meanwhile. Fixed (S, seq) shapes
        # with zero-pad rows keep per-row embeddings bit-exact regardless
        # of group composition (the tower pads to its own batch anyway).
        item = ("embed_queries", tokens, gid)
        qfut = None if degrade_only else eng._tower_submit(item)
        if eng._faults is not None:
            eng._faults.fire("cheap_embed")
        with eng._span("serve.cheap_embed", group=gid):
            q_d = jnp.asarray(eng.cheap.embed(tokens))
        width1 = np.where(quota_g > 0, np.maximum(32, nseed_g), 1
                          ).astype(np.int32)
        pool1 = _round_capacity(int(max(width1.max(), nseed_g.max())))
        with eng._span("serve.stage1", group=gid):
            res1 = eng._stage1(
                q_d, width=jnp.asarray(width1), pool=pool1,
                max_steps=jnp.asarray(4 * width1 * (quota_g > 0)))
            lane = np.arange(res1.pool_ids.shape[1], dtype=np.int32)
            seed_cap = _round_capacity(int(nseed_g.max()))
            seeds = np.asarray(jnp.where(
                jnp.asarray(lane[None, :] < nseed_g[:, None]),
                res1.pool_ids, -1))[:, :seed_cap]
            proxy_ids = np.asarray(res1.pool_ids)
            proxy_dists = np.asarray(res1.pool_dists)
            d_calls = np.asarray(res1.n_calls)
        if degrade_only:
            self._finish_degraded_group(valid, proxy_ids, proxy_dists,
                                        d_calls)
            return None
        try:
            with eng._span("serve.query_wait", group=gid):
                q_D = np.asarray(eng._tower_result(qfut, item, pool=self))
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException:
            if eng.on_tower_failure == "degrade":
                # the proxy ranking is already in hand — resolve the
                # group degraded instead of failing it
                self._finish_degraded_group(valid, proxy_ids, proxy_dists,
                                            d_calls)
                return None
            raise
        return _Prepared(
            valid=valid, seeds=seeds, quota=quota_g, nseed=nseed_g,
            d_calls=d_calls, q_D=q_D,
            proxy_ids=proxy_ids, proxy_dists=proxy_dists)

    def _finish_degraded_group(self, valid, proxy_ids, proxy_dists,
                               d_calls) -> None:
        """Resolve a staged admission group proxy-only (stage-1 ranking,
        ``degraded=True``) without it ever occupying a slot — the
        open-circuit serving mode. quota-0 rows resolve empty, exactly as
        they would fault-free."""
        eng = self.eng
        now = time.monotonic()
        for pend, s in valid:
            kk = int(pend.req.k)
            ids = np.asarray(proxy_ids[s, :kk], np.int64)
            dd = np.asarray(proxy_dists[s, :kk], np.float64)
            if int(pend.req.quota) <= 0:
                ids, dd = ids[:0], dd[:0]
            ok = (ids >= 0) & np.isfinite(dd)
            stats = ServeStats(
                d_calls=int(d_calls[s]), D_calls=0,
                queue_ms=(now - pend.t_submit) * 1e3, compute_ms=0.0,
                degraded=True)
            pend.future._resolve(SearchResult(ids[ok], dd[ok], stats))
        with eng._mu:
            eng._counters.degraded += len(valid)
            eng._counters.completed += len(valid)

    def admit(self, prep: _Prepared) -> None:
        """Recycle the group's slots in the resident state and pay the entry
        wave (``reset_slots`` + entry drain + commit). Rows outside the
        group are untouched bit-for-bit."""
        eng = self.eng
        now = time.monotonic()
        depth = eng._queue_depth()
        for pend, s in prep.valid:
            r = pend.req
            q = int(r.quota)
            ns = int(prep.nseed[s])
            self.quota[s] = q
            if eng.index_kind == "covertree":
                # level descent: no beam/step budget — termination is the
                # eps rule or the level cap, both applied by step_ct
                self.L[s] = beam.NO_QUOTA
                self.ms[s] = beam.NO_QUOTA
                self.ct_level[s] = 0
            else:
                self.L[s] = max(int(r.k), min(q, 2 * ns + 8))
                self.ms[s] = 4 * q
            self.k[s] = int(r.k)
            self.ew[s] = max(1, int(r.expand_width))
            self.occupied[s] = True
        for pend, s in prep.valid:
            self.active_req[s] = _Active(
                pend=pend, t_admit=now, d_calls=int(prep.d_calls[s]),
                occ_snap=int(self.occupied.sum()), depth_snap=depth,
                proxy_ids=(None if prep.proxy_ids is None
                           else prep.proxy_ids[s].copy()),
                proxy_dists=(None if prep.proxy_dists is None
                             else prep.proxy_dists[s].copy()))
        if self.q_D is None or self.q_D.shape[1] != prep.q_D.shape[1]:
            self.q_D = np.zeros((self.S, prep.q_D.shape[1]), prep.q_D.dtype)
        for _, s in prep.valid:
            self.q_D[s] = prep.q_D[s]

        # dedup backend: resolved once (first admission), then only the
        # sorted capacity grows — switching backends mid-residency would
        # force a full state rebuild for zero semantic gain (they are
        # bit-exact to each other)
        if self.dedup is None:
            self.dedup, self.cap = beam.resolve_dedup(
                eng.dedup, _round_capacity(int(self.quota.max())),
                self.quota, eng.n, drive="host")
        elif self.dedup == "sorted":
            need = _round_capacity(int(self.quota.max()))
            if self.cap is not None and need > self.cap:
                self.cap = need
                if self.state is not None:
                    self.state = beam.grow_state(
                        self.state, set_capacity=need)
        if eng.index_kind == "covertree":
            # pool = the memoized D-call set (bounded by quota and N), never
            # smaller than the root cover or the static plan chunk
            p_need = max(_round_capacity(int(max(
                int(self.k.max()), eng._flat.root_ids.shape[0],
                min(eng.n, int(self.quota.max()))))), eng._ct_chunk)
        else:
            p_need = _round_capacity(int(max(self.L.max(), self.k.max())))
        if self.state is None:
            self.pool_size = max(p_need, 1)
            empty = np.full((self.S, 1), -1, np.int32)
            zeros = np.zeros((self.S,), np.int32)
            if eng._stepper is not None:
                self.state, _, _ = eng._stepper.init(
                    empty, zeros, pool_size=self.pool_size,
                    dedup=self.dedup, set_capacity=self.cap)
            else:
                self.state, _, _ = _init_j(
                    jnp.asarray(empty), jnp.asarray(zeros),
                    n_points=eng.n, pool_size=self.pool_size,
                    dedup=self.dedup, set_capacity=self.cap)
        elif p_need > self.pool_size:
            self.pool_size = p_need
            self.state = beam.grow_state(self.state, pool_size=p_need)

        reset = np.zeros(self.S, bool)
        for _, s in prep.valid:
            reset[s] = True
        wave = self._next_wave()
        with eng._span("serve.wave", wave=wave, entry=1):
            with eng._span("serve.plan", wave=wave):
                quota_j = jnp.asarray(self.quota)
                if eng._stepper is not None:
                    self.state, safe, keep = eng._stepper.admit(
                        self.state, reset, prep.seeds, quota_j)
                else:
                    self.state, safe, keep = _admit_j(
                        self.state, jnp.asarray(reset),
                        jnp.asarray(prep.seeds), quota_j)
                safe_np, keep_np = np.asarray(safe), np.asarray(keep)
            if self._finish_wave(wave, safe, keep, safe_np, keep_np,
                                 overlap=False):
                self.sweep_early()
        with eng._mu:
            eng._counters.admitted += len(prep.valid)
            eng._counters.slot_occupancy = int(self.occupied.sum())

    # ----------------------------------------------------------------- step
    def _overlap_prepare(self) -> None:
        """Stage the next admission group while the tower drains (the slot
        pool's compute overlap) — at most once per in-flight drain."""
        eng = self.eng
        if self.prepared is None and not eng._closed:
            free = int((~self.occupied).sum())
            group = eng._pop_group(free) if free else []
            if group:
                self.prepared = self.prepare(group)

    def _next_wave(self) -> int:
        """Count one more wave; its number is the wave's span id."""
        with self.eng._mu:
            self.eng._counters.waves += 1
            return self.eng._counters.waves

    def _drain_wave(self, ids: np.ndarray, wave: int, *,
                    overlap: bool) -> bool:
        """One wave drain through the tower lane with bounded
        exponential-backoff retries (transient failures) and breaker
        accounting. Returns False when the lane gave up — breaker open,
        retries exhausted, non-retryable error, or drain timeout — with
        the terminal exception stashed for :meth:`tower_down` to chain
        onto the affected futures."""
        eng = self.eng
        if eng._breaker.blocked():
            self._tower_exc = TowerFailure(
                "expensive-tower lane is open-circuit "
                f"({eng._breaker.failures} consecutive failures)")
            if overlap:
                self._overlap_prepare()
            return False
        item = ("drain", ids, wave)
        fut = eng._tower_submit(item)
        if overlap:
            self._overlap_prepare()
        try:
            with eng._span("serve.drain_wait", wave=wave):
                eng._tower_result(fut, item, pool=self)
            return True
        except (KeyboardInterrupt, SystemExit):
            raise
        except BaseException as exc:
            self._tower_exc = exc
            return False

    def _finish_wave(self, wave: int, safe, keep, safe_np: np.ndarray,
                     keep_np: np.ndarray, *, overlap: bool) -> bool:
        """A planned wave's tail: charge its document lookups, drain the
        fresh ones through the tower lane (staging the next admission
        group meanwhile when ``overlap``), gather the wave's embeddings
        from the host cache, score and commit on device. Returns False
        when the tower lane gave up — :meth:`tower_down` has then resolved
        the residents and the wave is not committed."""
        eng = self.eng
        lookups = safe_np[keep_np]
        with eng._mu:
            eng._counters.doc_lookups += lookups.size
        if not self._drain_wave(lookups, wave, overlap=overlap):
            self.tower_down()
            return False
        with eng._span("serve.gather", wave=wave):
            doc = jnp.asarray(eng._doc_embs(safe_np, self.q_D.shape[1]))
        with eng._span("serve.commit", wave=wave):
            dists = _wave_dists_j(doc, jnp.asarray(self.q_D))
            if eng._stepper is not None:
                self.state = eng._stepper.commit(self.state, safe, keep,
                                                 dists)
            else:
                self.state = _commit_j(self.state, safe, keep, dists,
                                       backend=eng.backend)
        return True

    def step(self) -> None:
        """One plan/drain/commit wave over every occupied slot. While the
        tower drains the wave's fresh documents, the drive thread prepares
        the next admission group (cheap embed + stage 1) — the slot pool's
        compute overlap. A drain the tower lane gives up on fails only the
        resident requests (per ``on_tower_failure``) via
        :meth:`tower_down`; mid-flight deadline expiries resolve during
        the drain wait and their rows are swept after the commit."""
        eng = self.eng
        if eng.index_kind == "covertree":
            return self.step_ct()
        wave = self._next_wave()
        with eng._span("serve.wave", wave=wave, entry=0):
            self.ew_cap = max(self.ew_cap, int(self.ew.max()))
            with eng._span("serve.plan", wave=wave):
                quota_j = jnp.asarray(self.quota)
                L_j = jnp.asarray(self.L)
                ms_j = jnp.asarray(self.ms)
                if eng._stepper is not None:
                    self.state, safe, keep, _ = eng._stepper.plan(
                        self.state, eng._adjacency, quota_j, L_j, ms_j,
                        expand_width=jnp.asarray(self.ew),
                        expand_cap=self.ew_cap)
                else:
                    self.state, safe, keep, _ = _plan_step_j(
                        self.state, eng._adjacency, quota_j, L_j, ms_j,
                        jnp.asarray(self.ew), expand_cap=self.ew_cap)
                safe_np, keep_np = np.asarray(safe), np.asarray(keep)
            if self._finish_wave(wave, safe, keep, safe_np, keep_np,
                                 overlap=True):
                self.sweep_early()

    def step_ct(self) -> None:
        """One cover-tree level for every slot still descending.

        Per stepping row: size the frontier (pool prefix within the previous
        level's radius), re-open it, plan the level's fanout in chunk-wide
        waves (commits deferred past the last plan, so finer points cannot
        displace true frontier members mid-level), drain/commit each wave,
        then advance the row's level — the ε-criterion or the level cap
        freezes a finished row via ``ms = 0`` so ``resolve_finished`` picks
        it up. Rows at different levels ride the same waves; each row's
        chunk schedule depends only on its own frontier, which is what keeps
        a slot row bit-exact vs the synchronous drive."""
        eng = self.eng
        radii = eng._ct_radii
        l1 = eng._flat.depth - 1
        chunk = eng._ct_chunk
        stepping = self.occupied & (self.ms > 0)
        if l1 == 0:
            self.ms[stepping] = 0
            return
        wave = self._next_wave()
        with eng._span("serve.wave", wave=wave, entry=0):
            with eng._span("serve.plan", wave=wave):
                planned = self._plan_ct_level(stepping, chunk, l1)
            for i, (safe, keep, safe_np, keep_np) in enumerate(planned):
                if not self._finish_wave(wave, safe, keep, safe_np, keep_np,
                                         overlap=(i == 0)):
                    return
            pd0 = np.asarray(self.state.pool_dists[:, 0], np.float64)
            cont = np.zeros(self.S, bool)
            t = self.ct_level.copy()
            for s in np.nonzero(stepping)[0]:
                tt = int(t[s])
                if tt >= l1:
                    self.ms[s] = 0
                    continue
                self.ct_level[s] = tt + 1
                stop = not (pd0[s] < radii[tt] * (1.0 + 1.0 / eng.ct_eps))
                if stop or tt + 1 >= l1:
                    self.ms[s] = 0
                else:
                    cont[s] = True
            # rows still descending keep an open frontier so active_mask
            # holds them resident even when a level admitted nothing fresh
            # (the next level's child rows may still reach new points).
            # Rows resolved early mid-level (deadline) stay frozen.
            cont &= ~self.early
            if cont.any():
                if eng._stepper is not None:
                    self.state = eng._stepper.reopen(self.state,
                                                     jnp.asarray(cont))
                else:
                    self.state = _reopen_j(self.state, jnp.asarray(cont))
            self.sweep_early()

    def _plan_ct_level(self, stepping: np.ndarray, chunk: int,
                       l1: int) -> list:
        """Size each stepping row's frontier at its level, re-open it and
        plan the level's chunk-wide waves; returns each wave's
        ``(safe, keep)`` on device and on the host."""
        eng = self.eng
        quota_j = jnp.asarray(self.quota)
        L_j = jnp.asarray(self.L)
        ms_j = jnp.asarray(self.ms)
        t = self.ct_level
        radius = np.where(t == 0, np.inf,
                          eng._ct_radii[np.maximum(t - 1, 0)]
                          ).astype(np.float32)
        ew_t = np.asarray(_frontier_j(self.state.pool_dists,
                                      jnp.asarray(radius)))
        ew_t = np.where(stepping, ew_t, 0).astype(np.int32)
        if eng._stepper is not None:
            self.state = eng._stepper.reopen(self.state,
                                             jnp.asarray(stepping))
        else:
            self.state = _reopen_j(self.state, jnp.asarray(stepping))
        lev = jnp.asarray(np.minimum(t, l1 - 1).astype(np.int32))
        planned = []
        remaining = ew_t.copy()
        while remaining.max() > 0:
            ew = np.minimum(remaining, chunk).astype(np.int32)
            if eng._stepper is not None:
                self.state, safe, keep, _ = eng._stepper.plan(
                    self.state, eng._ct_children, quota_j, L_j, ms_j,
                    expand_width=jnp.asarray(ew), expand_cap=chunk,
                    level=lev, wave_dedup=False)
            else:
                self.state, safe, keep, _ = _plan_ct_j(
                    self.state, eng._ct_children, lev, quota_j, L_j, ms_j,
                    jnp.asarray(ew), expand_cap=chunk)
            planned.append((safe, keep))
            remaining -= ew
        return [(safe, keep, np.asarray(safe), np.asarray(keep))
                for safe, keep in planned]

    # ------------------------------------------------- degradation/deadlines
    def has_deadlines(self) -> bool:
        """Any resident request carrying a ``deadline_ms`` (drives the
        polling tower wait — fault-free deadline-less serving keeps the
        cheap blocking wait)."""
        for s in np.nonzero(self.occupied & ~self.early)[0]:
            a = self.active_req[s]
            if a is not None and a.pend.req.deadline_ms is not None:
                return True
        return False

    def _degraded_rows(self, a: _Active, s: int, ids_all, dd_all):
        """Best available ranking for a degraded resolution of slot ``s``:
        the stage-1 proxy pool when one exists (vamana), else the slot's
        current D-scored pool prefix (covertree — already ground-truth
        distances, just short of the full descent)."""
        if a.proxy_ids is not None:
            return a.proxy_ids, a.proxy_dists
        return ids_all[s], dd_all[s]

    def _resolve_degraded(self, s: int, ids_row, dd_row, *, now,
                          D_calls: int) -> None:
        """Resolve slot ``s``'s future with ``degraded=True`` stats from the
        given ranking. Does not free the slot — callers mark ``early`` and
        sweep at the next safe point."""
        a = self.active_req[s]
        r = a.pend.req
        kk = int(r.k)
        ids = np.asarray(ids_row[:kk], np.int64)
        dd = np.asarray(dd_row[:kk], np.float64)
        ok = (ids >= 0) & np.isfinite(dd)
        stats = ServeStats(
            d_calls=a.d_calls, D_calls=D_calls,
            queue_ms=(a.t_admit - a.pend.t_submit) * 1e3,
            compute_ms=(now - a.t_admit) * 1e3,
            slot_occupancy=a.occ_snap, queue_depth=a.depth_snap,
            degraded=True)
        a.pend.future._resolve(SearchResult(ids[ok], dd[ok], stats))

    def expire_inflight(self, *, defer_free: bool = False) -> None:
        """Mid-flight deadline enforcement: resolve every resident slot
        whose deadline has passed — degraded (proxy ranking) under
        ``on_tower_failure="degrade"``, :class:`DeadlineExceeded` under
        ``"fail"`` — and close its frontier (``beam.early_resolve``) so the
        row stops consuming waves. With ``defer_free=True`` (called from
        inside a tower wait, a wave in flight) the rows are only marked
        ``early``; the commit path sweeps them afterward, so the in-flight
        wave never races a re-admission into the same row."""
        eng = self.eng
        if self.state is None:
            return
        now = time.monotonic()
        rows = np.zeros(self.S, bool)
        for s in np.nonzero(self.occupied & ~self.early)[0]:
            a = self.active_req[s]
            dl = a.pend.req.deadline_ms
            if dl is None or (now - a.pend.t_submit) * 1e3 <= dl:
                continue
            rows[s] = True
        if not rows.any():
            return
        ids_all = np.asarray(self.state.pool_ids)
        dd_all = np.asarray(self.state.pool_dists)
        calls = np.asarray(self.state.n_calls)
        degraded = 0
        failed = 0
        for s in np.nonzero(rows)[0]:
            a = self.active_req[s]
            if eng.on_tower_failure == "degrade":
                ids_row, dd_row = self._degraded_rows(a, s, ids_all, dd_all)
                self._resolve_degraded(s, ids_row, dd_row, now=now,
                                       D_calls=int(calls[s]))
                degraded += 1
            else:
                a.pend.future._fail(DeadlineExceeded(
                    f"deadline {a.pend.req.deadline_ms} ms exceeded "
                    "mid-flight"))
                failed += 1
            self.early[s] = True
        # close the expired rows' frontiers so active_mask drops them; the
        # other rows' state is untouched bit-for-bit
        self.state = beam.early_resolve(self.state, jnp.asarray(rows))
        with eng._mu:
            eng._counters.deadline_misses += degraded + failed
            eng._counters.degraded += degraded
            eng._counters.completed += degraded
        if not defer_free:
            self.sweep_early()

    def sweep_early(self) -> None:
        """Free the rows whose futures resolved early, now that no wave is
        in flight over them."""
        if not self.early.any():
            return
        for s in np.nonzero(self.early)[0]:
            self.free_slot(s)
        self.early[:] = False
        with self.eng._mu:
            self.eng._counters.slot_occupancy = int(self.occupied.sum())

    def tower_down(self) -> None:
        """The tower lane gave up on a drain (retries exhausted, breaker
        open, timeout, or a non-retryable error): apply the engine's
        ``on_tower_failure`` policy to every resident request instead of
        poisoning the engine. ``"degrade"`` resolves each slot proxy-only;
        ``"fail"`` fails each slot's future with :class:`TowerFailure`
        chaining the original error. Either way the resident state stays
        consistent (the failed wave was never committed) and the engine
        keeps serving."""
        eng = self.eng
        exc = self._tower_exc or TowerFailure("expensive-tower lane failed")
        self._tower_exc = None
        now = time.monotonic()
        ids_all = np.asarray(self.state.pool_ids)
        dd_all = np.asarray(self.state.pool_dists)
        calls = np.asarray(self.state.n_calls)
        degraded = 0
        failed = 0
        rows = self.occupied & ~self.early
        for s in np.nonzero(rows)[0]:
            a = self.active_req[s]
            if eng.on_tower_failure == "degrade":
                ids_row, dd_row = self._degraded_rows(a, s, ids_all, dd_all)
                self._resolve_degraded(s, ids_row, dd_row, now=now,
                                       D_calls=int(calls[s]))
                degraded += 1
            else:
                err = TowerFailure(
                    "expensive-tower drain failed; request resolved "
                    "against policy on_tower_failure='fail' (see __cause__)")
                err.__cause__ = exc
                a.pend.future._fail(err)
                failed += 1
            self.early[s] = True
        self.sweep_early()
        with eng._mu:
            eng._counters.degraded += degraded
            eng._counters.completed += degraded
            eng._counters.shed += failed

    # -------------------------------------------------------------- resolve
    def resolve_finished(self) -> None:
        """Free every occupied slot that went inactive this step: read its
        pool prefix, stamp stats, resolve the future *now* (mid-flight —
        the slot is immediately reusable by the next admission)."""
        eng = self.eng
        if self.state is None or not self.occupied.any():
            return
        quota_j = jnp.asarray(self.quota)
        L_j = jnp.asarray(self.L)
        ms_j = jnp.asarray(self.ms)
        if eng._stepper is not None:
            act = np.asarray(eng._stepper.active(
                self.state, quota_j, L_j, ms_j))
        else:
            act = np.asarray(_active_j(self.state, quota_j, L_j, ms_j))
        fin = self.occupied & ~act & ~self.early
        if not fin.any():
            return
        with eng._span("serve.resolve", resolved=int(fin.sum())):
            ids_all = np.asarray(self.state.pool_ids)
            dd_all = np.asarray(self.state.pool_dists)
            calls = np.asarray(self.state.n_calls)
            now = time.monotonic()
            done = 0
            misses = 0
            for s in np.nonzero(fin)[0]:
                a = self.active_req[s]
                r = a.pend.req
                kk = int(r.k)
                row_ids = ids_all[s, :kk].astype(np.int64)
                row_dd = dd_all[s, :kk].astype(np.float64)
                ok = (row_ids >= 0) & np.isfinite(row_dd)
                stats = ServeStats(
                    d_calls=a.d_calls, D_calls=int(calls[s]),
                    queue_ms=(a.t_admit - a.pend.t_submit) * 1e3,
                    compute_ms=(now - a.t_admit) * 1e3,
                    slot_occupancy=a.occ_snap, queue_depth=a.depth_snap)
                if (r.deadline_ms is not None
                        and (now - a.pend.t_submit) * 1e3 > r.deadline_ms):
                    misses += 1  # admitted late: resolve, count the miss
                a.pend.future._resolve(
                    SearchResult(row_ids[ok], row_dd[ok], stats))
                done += 1
                self.free_slot(s)
            with eng._mu:
                eng._counters.completed += done
                eng._counters.deadline_misses += misses
                eng._counters.slot_occupancy = int(self.occupied.sum())

    def free_slot(self, s: int) -> None:
        self.occupied[s] = False
        self.active_req[s] = None
        self.quota[s] = 0
        self.L[s] = 1
        self.ms[s] = 0
        self.k[s] = 1
        self.ew[s] = 1
        self.ct_level[s] = 0

    def fail_all(self, exc: BaseException) -> None:
        """Genuinely poisoned resident state (an error outside the isolated
        tower/admission paths): fail every resident + staged future with
        :class:`EngineFailure` chaining the original traceback, drop the
        state. The engine survives — the next admission re-initializes a
        fresh resident state. This is the last resort; tower failures are
        handled per-slot by :meth:`tower_down` and never land here."""
        eng = self.eng

        def _wrap() -> EngineFailure:
            err = EngineFailure(
                "engine drive loop failed; resident state dropped "
                "(see __cause__)")
            err.__cause__ = exc
            return err

        if self.prepared is not None:
            for pend, _ in self.prepared.valid:
                if not pend.future.done():
                    pend.future._fail(_wrap())
            self.prepared = None
        for s in np.nonzero(self.occupied)[0]:
            if not self.early[s]:
                self.active_req[s].pend.future._fail(_wrap())
            self.free_slot(s)
        self.early[:] = False
        self._tower_exc = None
        self.state = None
        with eng._mu:
            eng._counters.slot_occupancy = 0


class BiMetricEngine:
    """corpus_tokens: (N, S) int32 document tokens.

    ``shards > 1`` runs the device side of **both** stages device-parallel
    over a corpus mesh. Stage 1 is :func:`repro.core.beam.sharded_greedy_search`
    (corpus split across ``shards`` devices, pools replicated). Stage 2
    keeps its host drive loop — the metric is the expensive tower itself —
    but all its bookkeeping (plan, dedup lookup/insert, commit, slot
    admission) runs inside the mesh via
    :class:`repro.core.beam.ShardedStepper`. Results are bit-exact vs
    ``shards=1``.

    ``dedup`` selects stage 2's dedup-state backend: ``"sorted"`` carries a
    quota-proportional (B, quota) sorted membership set through the wave
    (capacity = the max quota rounded up to a power of two, so mixed
    budgets retrace at most log-many times; quota-0 padding rows ride along
    with zero insertions), ``"bitmap"`` the dense (B, N) bitmap, and
    ``"auto"`` (default) picks sorted whenever the quota bound is below N.
    Under ``shards > 1`` the sorted set is replicated like the pools. Both
    backends are bit-exact to each other. Stage 1 (quota-unbounded proxy
    search) always keeps the bitmap, per the same auto rule.

    ``backend`` selects the device-side kernel route for stage-1 wave
    scoring and the pool merges (``repro.kernels.resolve_backend`` values):
    ``"ref"`` (default) keeps the frozen-oracle numerics every parity
    guarantee is stated against; ``"auto"`` is the deployment knob.
    ``quantize`` (``"int8"`` / ``"fp8"`` / ``"fp8_e5m2"``) holds the
    stage-1 corpus in quantized residency (built once per engine lifetime);
    stage 2 is never quantized.

    ``slots`` (default ``max_batch``) sizes the async drive's persistent
    slot pool — the resident (S,)-row search state whose rows are recycled
    per request (see the module doc). ``max_wait_ms`` bounds the idle
    drive's poll interval. ``max_inflight`` configured the retired
    fixed-wave double buffer and is now inert (accepted for
    compatibility); the slot pool always overlaps the tower drain with the
    next admission group's stage-1 work. All of these are inert for the
    synchronous ``query*`` paths.

    **Fault tolerance** (async path; see ``repro.serve``'s "Failure
    semantics"): transient expensive-tower failures are retried up to
    ``tower_retries`` times with exponential backoff starting at
    ``retry_backoff_ms``; ``breaker_threshold`` consecutive failures open
    a circuit breaker on the tower lane for ``breaker_cooldown_ms``
    (half-open probes re-close it). ``on_tower_failure`` picks what a
    given-up tower call does to the affected requests: ``"fail"``
    (default) fails their futures with :class:`TowerFailure`,
    ``"degrade"`` resolves them with stage-1 proxy-ranked results
    (``ServeStats.degraded``). ``drain_timeout_ms`` bounds any single
    tower call (a hung drain becomes :class:`TowerTimeout`, never retried
    inline). ``faults`` accepts a ``repro.serve.faults.FaultPlan``
    (test/benchmark-only deterministic fault injection).
    """

    def __init__(self, cheap: EmbedTower, expensive: EmbedTower,
                 corpus_tokens: np.ndarray,
                 index_cfg: vamana.VamanaConfig | None = None,
                 tower_batch: int = 64, shards: int = 1,
                 max_batch: int = 8, max_wait_ms: float = 5.0,
                 max_inflight: int = 2, dedup: str = "auto",
                 backend="ref", quantize: str | None = None,
                 slots: int | None = None, index: str = "vamana",
                 covertree_eps: float = 0.5, covertree_T: float = 2.0,
                 on_tower_failure: str = "fail", tower_retries: int = 3,
                 retry_backoff_ms: float = 25.0, breaker_threshold: int = 5,
                 breaker_cooldown_ms: float = 2000.0,
                 drain_timeout_ms: float | None = None,
                 faults: "serve_faults.FaultPlan | None" = None):
        self.cheap = cheap
        self.expensive = expensive
        self.corpus_tokens = corpus_tokens
        self.n = corpus_tokens.shape[0]
        self.tower_batch = tower_batch
        self.shards = shards
        if dedup not in ("auto", "sorted", "bitmap"):
            raise ValueError(f"unknown dedup backend {dedup!r}")
        self.dedup = dedup
        self.backend = kernels.resolve_backend(
            backend, quantize=quantize, _caller="serve.BiMetricEngine")
        self.max_batch = max_batch
        self.slots = int(slots if slots is not None else max_batch)
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        self.max_wait = max_wait_ms / 1e3
        self.max_inflight = max(1, max_inflight)  # retired knob, kept inert
        if index not in ("vamana", "covertree"):
            raise ValueError(f"unknown index kind {index!r}")
        self.index_kind = index
        self.ct_eps = float(covertree_eps)
        if on_tower_failure not in ("fail", "degrade"):
            raise ValueError(
                f"unknown on_tower_failure policy {on_tower_failure!r}")
        self.on_tower_failure = on_tower_failure
        self.tower_retries = max(0, int(tower_retries))
        self.retry_backoff_s = max(0.0, retry_backoff_ms / 1e3)
        self.drain_timeout_s = (None if drain_timeout_ms is None
                                else max(0.0, drain_timeout_ms / 1e3))
        self._breaker = serve_faults.CircuitBreaker(
            threshold=breaker_threshold,
            cooldown_s=breaker_cooldown_ms / 1e3)
        self._faults = faults
        # --- index build: cheap metric ONLY --------------------------------
        self.emb_d = jnp.asarray(cheap.embed(corpus_tokens))
        if index == "covertree":
            # Algorithm 2 on the cheap embeddings (offline, per-query NumPy
            # — the query path is the batched engine); the flattened layout
            # is what the plan/commit programs index with static shapes
            tree = covertree.build(
                np.asarray(self.emb_d, np.float64), T=covertree_T)
            self._flat = covertree.flatten(tree)
            self._ct_children = jnp.asarray(self._flat.children)
            self._ct_radii = np.asarray(self._flat.radii, np.float64)
            self._ct_chunk = covertree.wave_chunk(self._flat.fanout)
            self.index = None
            self._corpus_d = None
            self._adjacency = None
        else:
            self._flat = None
            self.index = vamana.build(self.emb_d,
                                      index_cfg or vamana.VamanaConfig(
                                          max_degree=16, l_build=24,
                                          pool_size=48, rev_candidates=16))
            # what stage 1 scores (an operand of _stage1_j): the matmul
            # backends thread the corpus-norm cache (built ONCE here, like
            # the index) through every wave; with quantize= the view is
            # built quantized, also once — the graph is still built on the
            # exact embeddings, only wave scoring is lossy
            need_view = (self.backend.matmul
                         or self.backend.quantize is not None)
            self._corpus_d = (kernels.as_corpus_view(
                self.emb_d, quantize=self.backend.quantize)
                if need_view else self.emb_d)
            self._adjacency = self.index.adjacency.astype(jnp.int32)
        # one mesh for the engine lifetime; stage 2 steps through the same
        # mesh as stage 1 (ShardedStepper = the in-mesh plan/commit programs)
        self._mesh = (sharding.search_mesh(shards) if shards > 1 else None)
        self._stepper = (beam.ShardedStepper(
            shards=shards, n_points=self.n, mesh=self._mesh,
            backend=self.backend)
            if shards > 1 else None)
        # lazy expensive-tower document embeddings (engine-lifetime cache)
        self._emb_D: np.ndarray | None = None
        self._emb_D_valid = np.zeros((self.n,), bool)
        self._cache_lock = threading.Lock()
        # async slot-pool state (threads start lazily on the first submit).
        # _mu guards the admission queue + counters; the lifecycle lock
        # orders start/close vs submit. Lock order: lifecycle -> _mu.
        self._lifecycle_lock = threading.Lock()
        self._started = False
        self._closed = False
        self._threads: list[threading.Thread] = []
        self._mu = threading.RLock()
        self._q_cond = threading.Condition(self._mu)
        self._queue: list = []  # heap of (-priority, deadline, seq, _Pending)
        self._seq = 0
        self._counters = EngineCounters()
        self._tower_q: queue.Queue | None = None
        self._pool: _SlotPool | None = None
        self._tower_thread: threading.Thread | None = None

    # ------------------------------------------------------------ internals
    def _stage1(self, q_d: Array, *, width, pool: int,
                max_steps) -> beam.SearchResult:
        """Batched cheap-metric greedy search from the medoid (stage 1).

        ``width`` / ``max_steps`` are scalars or per-query (B,) vectors
        (request waves mix budgets), passed on as (B,) int32 operands;
        ``pool`` (>= max(width)) is the static pool size. On one device
        this is :func:`_stage1_j`, one of the jitted device-lane steps, with
        the corpus and graph as operands: traced and compiled once per
        (B, pool) bucket, never again at an admission. With ``shards > 1``
        the same loop runs device-parallel over the engine's corpus mesh —
        bit-exact vs the single-device path."""
        b = q_d.shape[0]
        entries = jnp.broadcast_to(
            jnp.asarray(self.index.medoid, jnp.int32).reshape(1, 1), (b, 1))
        if self.shards > 1:
            return beam.sharded_greedy_search(
                self._corpus_d, self._adjacency, q_d, entries,
                shards=self.shards, metric=_METRIC_D,
                mesh=self._mesh, beam_width=width, pool_size=pool,
                max_steps=max_steps, backend=self.backend)
        _stage1_caller.engine = self
        try:
            return _stage1_j(
                self._corpus_d, self._adjacency, q_d, entries,
                jnp.broadcast_to(jnp.asarray(width, jnp.int32), (b,)),
                jnp.broadcast_to(jnp.asarray(max_steps, jnp.int32), (b,)),
                n_points=self.n, pool_size=int(pool), metric=_METRIC_D,
                backend=self.backend)
        finally:
            _stage1_caller.engine = None

    def _drain_tower(self, ids: np.ndarray) -> None:
        """Embed not-yet-cached docs through the expensive tower, counting
        the rows and forward batches drained. Serialized by the cache lock
        (the tower lane is single-file by construction; the lock also
        covers synchronous callers running concurrently with the slot
        drive)."""
        with self._cache_lock:
            need = np.unique(
                ids[(ids >= 0) & ~self._emb_D_valid[np.maximum(ids, 0)]])
            if need.size == 0:
                return
            embs = self.expensive.embed(self.corpus_tokens[need],
                                        batch=self.tower_batch)
            if self._emb_D is None:
                self._emb_D = np.zeros((self.n, embs.shape[1]), embs.dtype)
            self._emb_D[need] = embs
            self._emb_D_valid[need] = True
            with self._mu:
                self._counters.drained_rows += need.size
                self._counters.drain_batches += -(-need.size
                                                  // self.tower_batch)

    def reset_doc_cache(self) -> None:
        """Drop the expensive-tower document cache (benchmark hygiene)."""
        with self._cache_lock:
            self._emb_D = None
            self._emb_D_valid[:] = False

    def _doc_embs(self, safe_np: np.ndarray, dim: int) -> np.ndarray:
        """(B, K, dim_D) gather from the host cache; rows a wave needs are
        guaranteed drained before the wave's commit runs."""
        emb = self._emb_D
        if emb is None:
            return np.zeros((*safe_np.shape, dim), np.float32)
        return emb[np.maximum(safe_np, 0)]

    # -------------------------------------------------------- wave coroutine
    def _wave_gen(self, query_tokens: np.ndarray, quota, k, n_seeds,
                  expand_width):
        """Dispatch the synchronous batch to the index kind's coroutine.

        Plain function (not a generator) so the dispatch runs eagerly;
        ``n_seeds`` / ``expand_width`` are vamana stage-1/2 knobs — the
        cover-tree descent seeds from the root cover and sizes its own
        frontier per level, so they are accepted and ignored there."""
        if self.index_kind == "covertree":
            return self._wave_gen_ct(query_tokens, quota, k)
        return self._wave_gen_vamana(query_tokens, quota, k, n_seeds,
                                     expand_width)

    def _wave_gen_vamana(self, query_tokens: np.ndarray, quota, k, n_seeds,
                         expand_width):
        """The two-stage search for one synchronous batch, as a coroutine.

        Yields tower-lane work items — ``("embed_queries", tokens)`` then one
        ``("drain", ids)`` per stage-2 wave — and receives the answer via
        ``send`` (the expensive query embeddings / nothing for a drain).
        Device-lane work (cheap embed, stage 1, plan/commit bookkeeping)
        runs between yields. Returns ``(ids, dists, stats)`` via
        ``StopIteration.value``. The async slot drive runs the identical
        per-row math against its resident state (same jitted programs, same
        per-row operands), which is what keeps the two drives bit-exact.
        """
        b = query_tokens.shape[0]
        quota_np = np.broadcast_to(
            np.asarray(quota, np.int32), (b,)).copy()
        n_seeds_np = (np.maximum(1, quota_np // 2) if n_seeds is None
                      else np.broadcast_to(
                          np.asarray(n_seeds, np.int32), (b,)).copy())
        k_np = np.broadcast_to(np.asarray(k, np.int32), (b,))
        ew_np = np.maximum(1, np.broadcast_to(
            np.asarray(expand_width, np.int32), (b,)))
        ew_cap = int(ew_np.max())

        q_d = jnp.asarray(self.cheap.embed(query_tokens))
        q_D = yield ("embed_queries", query_tokens)

        # stage 1 — one batched cheap-metric search on device; per-query
        # width/steps so a request's answer never depends on its wave-mates.
        # quota-0 rows (admission padding, or an explicit quota=0 request)
        # can never spend a D call, so they run a width-1, zero-step stage 1
        # — the padded partial-wave flush costs one lane, not a full search
        width1 = np.where(quota_np > 0, np.maximum(32, n_seeds_np), 1
                          ).astype(np.int32)
        pool1 = int(max(width1.max(), n_seeds_np.max()))
        res1 = self._stage1(
            q_d, width=jnp.asarray(width1), pool=pool1,
            max_steps=jnp.asarray(4 * width1 * (quota_np > 0)))
        lane = np.arange(res1.pool_ids.shape[1], dtype=np.int32)
        seeds = jnp.where(
            jnp.asarray(lane[None, :] < n_seeds_np[:, None]),
            res1.pool_ids, -1)[:, :int(n_seeds_np.max())]
        d_calls = np.asarray(res1.n_calls)

        # stage 2 — the core hot loop, host-driven: plan on device, drain the
        # tower for the wave's union of fresh docs, commit scores on device.
        L = np.maximum(
            k_np, np.minimum(quota_np, 2 * np.maximum(n_seeds_np, 1) + 8))
        P = int(max(L.max(), k_np.max()))
        max_steps = 4 * quota_np
        quota_j = jnp.asarray(quota_np)
        L_j = jnp.asarray(L)
        ms_j = jnp.asarray(max_steps)
        ew_j = jnp.asarray(ew_np)

        # dedup backend for the wave (host-driven drive: the non-donated
        # bitmap would be copied through every dispatch, so auto favors the
        # quota-proportional sorted set). Capacity is a static shape — the
        # pow2 rounding keeps retraces bounded, and quota-0 padding rows
        # never raise the wave's max.
        dedup, cap = beam.resolve_dedup(
            self.dedup, _round_capacity(int(quota_np.max())), quota_np,
            self.n, drive="host")

        stepper = self._stepper
        if stepper is not None:
            state, safe, keep = stepper.init(
                seeds, quota_j, pool_size=P, dedup=dedup, set_capacity=cap)
        else:
            state, safe, keep = _init_j(
                seeds, quota_j, n_points=self.n, pool_size=P, dedup=dedup,
                set_capacity=cap)
        while True:
            safe_np = np.asarray(safe)
            yield ("drain", safe_np[np.asarray(keep)])
            doc_embs = jnp.asarray(self._doc_embs(safe_np, q_D.shape[1]))
            dists = _wave_dists_j(doc_embs, q_D)
            if stepper is not None:
                state = stepper.commit(state, safe, keep, dists)
                if not stepper.active_any(state, quota_j, L_j, ms_j):
                    break
                state, safe, keep, _ = stepper.plan(
                    state, self._adjacency, quota_j, L_j, ms_j,
                    expand_width=ew_j, expand_cap=ew_cap)
            else:
                state = _commit_j(state, safe, keep, dists,
                                  backend=self.backend)
                if not bool(_active_any_j(state, quota_j, L_j, ms_j)):
                    break
                state, safe, keep, _ = _plan_step_j(
                    state, self._adjacency, quota_j, L_j, ms_j, ew_j,
                    expand_cap=ew_cap)

        kmax = int(k_np.max())
        ids = np.asarray(state.pool_ids[:, :kmax], np.int64)
        dd = np.asarray(state.pool_dists[:, :kmax], np.float64)
        D_calls = np.asarray(state.n_calls)
        stats = [ServeStats(d_calls=int(d_calls[i]), D_calls=int(D_calls[i]))
                 for i in range(b)]
        return ids, dd, stats

    def _wave_gen_ct(self, query_tokens: np.ndarray, quota, k):
        """Algorithm 3 for one synchronous batch, as a coroutine.

        Same tower-lane protocol as the vamana coroutine — one
        ``("embed_queries", tokens)`` then one ``("drain", ids)`` per
        level-chunk wave — but the device side is the cover-tree descent of
        :func:`repro.core.covertree.search_batched` (host-chunk drive): per
        level, size each row's frontier, re-open it, plan all chunk waves
        before committing any, then drain/score/commit each wave. Per-row
        math is independent of batch-mates and of the pool capacity, which
        is what keeps this bit-exact vs the async slot drive."""
        b = query_tokens.shape[0]
        quota_np = np.broadcast_to(np.asarray(quota, np.int32), (b,)).copy()
        k_np = np.broadcast_to(np.asarray(k, np.int32), (b,))
        q_D = yield ("embed_queries", query_tokens)

        flat = self._flat
        l1 = flat.depth - 1
        chunk = self._ct_chunk
        radii = self._ct_radii
        e0 = int(flat.root_ids.shape[0])
        # identical static shapes to the slot pool's p_need so the two
        # drives share jitted programs (capacity is invisible to a row)
        P = max(_round_capacity(int(max(
            int(k_np.max()), e0, min(self.n, int(quota_np.max()))))), chunk)
        dedup, cap = beam.resolve_dedup(
            self.dedup, _round_capacity(int(quota_np.max())), quota_np,
            self.n, drive="host")
        quota_j = jnp.asarray(quota_np)
        L_j = jnp.full((b,), beam.NO_QUOTA, jnp.int32)
        ms_j = jnp.full((b,), beam.NO_QUOTA, jnp.int32)
        entries = jnp.broadcast_to(
            jnp.asarray(flat.root_ids, jnp.int32)[None, :], (b, e0))
        stepper = self._stepper
        if stepper is not None:
            state, safe, keep = stepper.init(
                entries, quota_j, pool_size=P, dedup=dedup, set_capacity=cap)
        else:
            state, safe, keep = _init_j(
                entries, quota_j, n_points=self.n, pool_size=P,
                dedup=dedup, set_capacity=cap)

        def _commit(s, sf, kp):
            safe_np = np.asarray(sf)
            yield ("drain", safe_np[np.asarray(kp)])
            doc = jnp.asarray(self._doc_embs(safe_np, q_D.shape[1]))
            dists = _wave_dists_j(doc, q_D)
            if stepper is not None:
                return stepper.commit(s, sf, kp, dists)
            return _commit_j(s, sf, kp, dists, backend=self.backend)

        state = yield from _commit(state, safe, keep)
        alive = np.ones(b, bool)
        for t in range(l1):
            radius = np.inf if t == 0 else float(radii[t - 1])
            ew_t = np.asarray(_frontier_j(state.pool_dists,
                                          jnp.float32(radius)))
            ew_t = np.where(alive, ew_t, 0).astype(np.int32)
            if not ew_t.any():
                break
            if stepper is not None:
                state = stepper.reopen(state, jnp.asarray(alive))
            else:
                state = _reopen_j(state, jnp.asarray(alive))
            lev = jnp.full((b,), t, jnp.int32)
            planned = []
            remaining = ew_t.copy()
            while remaining.max() > 0:
                ew = np.minimum(remaining, chunk).astype(np.int32)
                if stepper is not None:
                    state, safe, keep, _ = stepper.plan(
                        state, self._ct_children, quota_j, L_j, ms_j,
                        expand_width=jnp.asarray(ew), expand_cap=chunk,
                        level=lev, wave_dedup=False)
                else:
                    state, safe, keep, _ = _plan_ct_j(
                        state, self._ct_children, lev, quota_j, L_j, ms_j,
                        jnp.asarray(ew), expand_cap=chunk)
                planned.append((safe, keep))
                remaining -= ew
            for safe, keep in planned:
                state = yield from _commit(state, safe, keep)
            dmin = np.asarray(state.pool_dists[:, 0], np.float64)
            alive &= dmin < radii[t] * (1.0 + 1.0 / self.ct_eps)

        kmax = int(k_np.max())
        ids = np.asarray(state.pool_ids[:, :kmax], np.int64)
        dd = np.asarray(state.pool_dists[:, :kmax], np.float64)
        D_calls = np.asarray(state.n_calls)
        stats = [ServeStats(d_calls=0, D_calls=int(D_calls[i]))
                 for i in range(b)]
        return ids, dd, stats

    def _service_tower(self, item):
        """Run one tower-lane work item (the expensive-tower forward passes):
        ``(kind, payload)``, and on the slot drive a third field, the id of
        the group or wave that asked for it."""
        kind, payload = item[:2]
        if self._faults is not None:
            # injection precedes the real work (and the doc-cache write), so
            # a retried drain recomputes from the same cache state — retries
            # stay bit-exact vs a fault-free run
            self._faults.fire(kind)
        if kind == "embed_queries":
            # query-side embeddings are not charged to the quota: the budget
            # counts *document* scorings (the paper's cost model)
            return jnp.asarray(self.expensive.embed(payload))
        return self._drain_tower(payload)  # "drain"

    def _drive_sync(self, gen):
        """Run a wave coroutine to completion, servicing tower work inline."""
        try:
            item = next(gen)
            while True:
                item = gen.send(self._service_tower(item))
        except StopIteration as stop:
            return stop.value

    # ---------------------------------------------------------------- query
    @staticmethod
    def _is_request_batch(obj) -> bool:
        return (isinstance(obj, (list, tuple)) and len(obj) > 0
                and all(isinstance(r, SearchRequest) for r in obj))

    def query_batch(self, requests=None, *, quota=None,
                    k: int = 10, n_seeds=None, expand_width=1):
        """Two-stage bi-metric search for a batch of requests, inline.

        Native form: a list of :class:`SearchRequest` -> a list of
        :class:`SearchResult` (per-request k, trimmed rows). Legacy form
        (deprecated, warns once): a (B, S) token array with ``quota`` /
        ``k`` / ``n_seeds`` / ``expand_width`` scalars-or-(B,) vectors ->
        the historical ``(ids (B, k), D-dists (B, k), [ServeStats])`` tuple
        with id -1 / dist +inf padding. Both run the identical wave; mixed
        budgets get exact per-query accounting either way.
        """
        if self._is_request_batch(requests):
            reqs = list(requests)
            tokens = np.stack([np.asarray(r.tokens) for r in reqs])
            quota_v = np.array([int(r.quota) for r in reqs], np.int32)
            k_v = np.array([int(r.k) for r in reqs], np.int32)
            nseed_v = np.array(
                [max(1, int(r.quota) // 2) if r.n_seeds is None
                 else max(1, int(r.n_seeds)) for r in reqs], np.int32)
            ew_v = np.array(
                [max(1, int(r.expand_width)) for r in reqs], np.int32)
            ids, dd, stats = self._drive_sync(
                self._wave_gen(tokens, quota_v, k_v, nseed_v, ew_v))
            out = []
            for i, r in enumerate(reqs):
                row_ids, row_dd = ids[i, :r.k], dd[i, :r.k]
                ok = (row_ids >= 0) & np.isfinite(row_dd)
                out.append(SearchResult(row_ids[ok], row_dd[ok], stats[i]))
            return out
        if isinstance(requests, SearchRequest):
            raise TypeError(
                "query_batch takes a list of SearchRequest; use "
                "query(request) for a single one")
        if quota is None:
            raise TypeError("legacy query_batch(tokens, ...) needs quota=")
        _warn_legacy("query_batch", "query_batch(tokens, quota=...)")
        return self._drive_sync(self._wave_gen(
            np.asarray(requests), quota, k, n_seeds, expand_width))

    def query(self, request=None, *, quota: int | None = None, k: int = 10,
              n_seeds: int | None = None) -> SearchResult:
        """One request, inline. Native form: ``query(SearchRequest)``.
        Legacy form (deprecated, warns once): ``query(tokens, quota=...)``.
        Returns a :class:`SearchResult` (tuple-unpacks as (ids, dists,
        stats), so legacy callers keep working)."""
        if isinstance(request, SearchRequest):
            return self.query_batch([request])[0]
        if quota is None:
            raise TypeError("legacy query(tokens, ...) needs quota=")
        _warn_legacy("query", "query(tokens, quota=...)")
        ids, dd, stats = self._drive_sync(self._wave_gen(
            np.asarray(request)[None], int(quota), int(k), n_seeds, 1))
        ok = (ids[0] >= 0) & np.isfinite(dd[0])
        return SearchResult(ids[0][ok], dd[0][ok], stats[0])

    # ------------------------------------------------------- async slot pool
    def submit(self, request=None, *, quota: int | None = None,
               k: int = 10, n_seeds: int | None = None,
               expand_width: int = 1, deadline_ms: float | None = None,
               priority: int = 0) -> ServeFuture:
        """Queue one request for the slot pool; returns a
        :class:`ServeFuture` resolving to a :class:`SearchResult`. Native
        form: ``submit(SearchRequest)``. Legacy form (deprecated, warns
        once): ``submit(tokens, quota=...)``. Starts the drive threads on
        first use; raises ``RuntimeError`` after :meth:`close`."""
        if not isinstance(request, SearchRequest):
            if quota is None:
                raise TypeError("legacy submit(tokens, ...) needs quota=")
            _warn_legacy("submit", "submit(tokens, quota=...)")
            request = SearchRequest(
                tokens=np.asarray(request), quota=int(quota), k=int(k),
                n_seeds=n_seeds, expand_width=expand_width,
                deadline_ms=deadline_ms, priority=priority)
        fut = ServeFuture()
        now = time.monotonic()
        pend = _Pending(req=request, future=fut, t_submit=now)
        deadline = (math.inf if request.deadline_ms is None
                    else now + request.deadline_ms / 1e3)
        # enqueue under the lifecycle lock: close() flips _closed under the
        # same lock before it cancels the queue, so a request can never land
        # behind the cancellation sweep unresolved
        with self._lifecycle_lock:
            self._ensure_started_locked()
            with self._q_cond:
                self._seq += 1
                heapq.heappush(
                    self._queue,
                    (-int(request.priority), deadline, self._seq, pend))
                self._counters.submitted += 1
                self._counters.queue_depth = len(self._queue)
                self._q_cond.notify_all()
        return fut

    def counters(self) -> EngineCounters:
        """Snapshot of the serving counters (cumulative since engine
        construction; ``queue_depth`` / ``slot_occupancy`` are
        instantaneous), with the towers' row counts read in."""
        with self._mu:
            c = self._counters
            snap = dataclasses.replace(c, span_n=dict(c.span_n),
                                       span_s=dict(c.span_s))
        snap.breaker_opens = self._breaker.opens
        for label in ("cheap", "expensive"):
            # a tower is anything with ``embed``; only an EmbedTower counts
            row_counts = getattr(getattr(self, label), "row_counts", None)
            if row_counts is not None:
                rows, useful = row_counts()
                setattr(snap, f"{label}_rows", rows)
                setattr(snap, f"{label}_rows_useful", useful)
        return snap

    @contextlib.contextmanager
    def _span(self, name: str, **ids):
        """One ``serve.*`` span: a profiler host span (``jax.profiler.
        TraceAnnotation``, on the device trace's clock, ``ids`` as its
        stats) and, as it closes, one more ``span_n[name]`` and its host
        seconds on ``span_s[name]`` in :class:`EngineCounters` — the
        record when no profiler runs. Adds no host/device sync."""
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(name, **ids):
                yield
        finally:
            dt = time.perf_counter() - t0
            with self._mu:
                n, sec = self._counters.span_n, self._counters.span_s
                n[name] = n.get(name, 0) + 1
                sec[name] = sec.get(name, 0.0) + dt

    def health(self) -> dict:
        """Operational snapshot: breaker state, degradation mode, queue and
        slot pressure, and the cumulative counters (as a dict). Safe to
        call from any thread; values are point-in-time reads (the breaker
        is single-writer — the drive thread — so the reads are coherent
        enough for monitoring)."""
        snap = self.counters()
        state = self._breaker.state
        return {
            "breaker_state": state,
            "consecutive_tower_failures": self._breaker.failures,
            "breaker_opens": self._breaker.opens,
            "degraded_mode": (state != "closed"
                              and self.on_tower_failure == "degrade"),
            "on_tower_failure": self.on_tower_failure,
            "queue_depth": snap.queue_depth,
            "slot_occupancy": snap.slot_occupancy,
            "started": self._started,
            "closed": self._closed,
            "counters": dataclasses.asdict(snap),
        }

    def close(self, timeout: float | None = 60.0) -> None:
        """Stop the slot pool. Requests already admitted to a slot (or
        staged for admission) still resolve; requests **still queued** are
        cancelled immediately — their ``result()`` raises
        ``CancelledError`` — instead of being flushed into a final drain
        that could outlive the timeout. Raises ``RuntimeError`` if the
        drive/tower threads fail to join within ``timeout`` (they are
        daemons, so the process still exits, but silent success would hide
        unresolved resident requests). Idempotent; ``submit`` raises
        afterwards."""
        with self._lifecycle_lock:
            already = self._closed
            self._closed = True
            started = self._started
            dropped: list[_Pending] = []
            if not already and started:
                with self._q_cond:
                    while self._queue:
                        dropped.append(heapq.heappop(self._queue)[-1])
                    self._counters.queue_depth = 0
                    self._counters.cancelled += len(dropped)
                    self._q_cond.notify_all()
        if already or not started:
            return
        for pend in dropped:  # outside the locks: cancel runs callbacks
            pend.future.cancel()
        for t in self._threads:
            t.join(timeout)
        stuck = [t.name for t in self._threads if t.is_alive()]
        if stuck:
            raise RuntimeError(
                f"engine threads failed to join within timeout={timeout}: "
                f"{stuck} (daemon threads — they die with the process, but "
                "resident requests may be unresolved)")

    def _ensure_started_locked(self) -> None:
        """Start the drive + tower threads on first use; caller holds
        ``_lifecycle_lock``."""
        if self._closed:
            raise RuntimeError("engine slot pool is closed")
        if self._started:
            return
        self._tower_q = queue.Queue()
        self._pool = _SlotPool(self)
        self._threads = [
            threading.Thread(target=loop, daemon=True, name=name)
            for name, loop in (("serve-drive", self._drive_loop),
                               ("serve-tower", self._tower_loop))]
        self._tower_thread = self._threads[1]
        for t in self._threads:
            t.start()
        self._started = True

    # ----------------------------------------------------- admission helpers
    def _queue_depth(self) -> int:
        with self._mu:
            return len(self._queue)

    def _pop_group(self, n: int) -> list[_Pending]:
        """Pop up to ``n`` requests in (priority, deadline, FIFO) order.
        Entries whose deadline already expired are failed here (never
        admitted) — the pop is an admission point."""
        now = time.monotonic()
        group: list[_Pending] = []
        expired: list[_Pending] = []
        with self._q_cond:
            while self._queue and len(group) < n:
                _, deadline, _, pend = heapq.heappop(self._queue)
                if deadline < now:
                    expired.append(pend)
                else:
                    group.append(pend)
            self._counters.queue_depth = len(self._queue)
            self._counters.deadline_misses += len(expired)
        for pend in expired:  # outside the lock: _fail runs callbacks
            pend.future._fail(DeadlineExceeded(
                f"deadline_ms={pend.req.deadline_ms} expired while queued"))
        return group

    def _expire_queued(self) -> None:
        """Fail every queued request whose deadline has passed (checked on
        every drive-loop iteration, so expiry does not wait for a free
        slot)."""
        now = time.monotonic()
        expired: list[_Pending] = []
        with self._q_cond:
            if not self._queue:
                return
            alive = [e for e in self._queue if e[1] >= now]
            if len(alive) == len(self._queue):
                return
            expired = [e[-1] for e in self._queue if e[1] < now]
            heapq.heapify(alive)
            self._queue = alive
            self._counters.queue_depth = len(alive)
            self._counters.deadline_misses += len(expired)
        for pend in expired:
            pend.future._fail(DeadlineExceeded(
                f"deadline_ms={pend.req.deadline_ms} expired while queued"))

    def _tower_submit(self, item) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()
        if self._tower_thread is not None and not self._tower_thread.is_alive():
            # lane thread died (e.g. an injected KeyboardInterrupt escaped):
            # fail fast instead of waiting forever on a queue nobody reads
            fut.set_exception(TowerFailure(
                "expensive-tower lane thread is dead"))
            return fut
        self._tower_q.put((item, fut))
        return fut

    def _await_tower(self, fut: concurrent.futures.Future, pool):
        """Wait for one tower-lane future. Fault-free deadline-less serving
        keeps the cheap fully-blocking wait; with resident deadlines or a
        ``drain_timeout_ms`` the wait polls every 20 ms so mid-flight
        expiries resolve *during* the tower call (``defer_free=True`` — the
        wave in flight still commits before the rows are recycled) and a
        hung call becomes :class:`TowerTimeout` after the timeout."""
        if self.drain_timeout_s is None and (
                pool is None or not pool.has_deadlines()):
            return fut.result()
        t0 = time.monotonic()
        while True:
            try:
                return fut.result(timeout=0.02)
            except concurrent.futures.TimeoutError:
                if pool is not None:
                    pool.expire_inflight(defer_free=True)
                if (self.drain_timeout_s is not None
                        and time.monotonic() - t0 > self.drain_timeout_s):
                    raise TowerTimeout(
                        f"tower call exceeded drain_timeout_ms="
                        f"{self.drain_timeout_s * 1e3:g}") from None

    def _tower_result(self, fut: concurrent.futures.Future, item,
                      pool=None):
        """Await a tower-lane call with bounded exponential-backoff retries
        and breaker accounting. Retries cover transient failures only (an
        exception whose ``transient`` attribute is falsy, or a
        :class:`TowerTimeout`, goes straight to the caller); each failure
        counts toward the breaker, each success closes it. The terminal
        exception propagates to the caller — the isolation boundary
        (:meth:`_SlotPool.tower_down` / admission policy) decides who it
        fails."""
        attempts = 0
        while True:
            try:
                out = self._await_tower(fut, pool)
            except (KeyboardInterrupt, SystemExit):
                raise
            except BaseException as exc:
                attempts += 1
                self._breaker.on_failure()
                with self._mu:
                    self._counters.tower_failures += 1
                retryable = (getattr(exc, "transient", True)
                             and not isinstance(exc, TowerTimeout))
                if (not retryable or attempts > self.tower_retries
                        or self._breaker.blocked()):
                    raise
                with self._mu:
                    self._counters.retries += 1
                time.sleep(self.retry_backoff_s * (2 ** (attempts - 1)))
                fut = self._tower_submit(item)
                continue
            self._breaker.on_success()
            return out

    # ----------------------------------------------------------- drive loops
    def _drive_loop(self) -> None:
        pool = self._pool
        try:
            while True:
                try:
                    self._expire_queued()
                    pool.expire_inflight()
                    if pool.prepared is not None:
                        prep, pool.prepared = pool.prepared, None
                        pool.admit(prep)
                        pool.resolve_finished()
                        continue
                    free = int((~pool.occupied).sum())
                    if free:
                        group = self._pop_group(free)
                        if group:
                            pool.prepared = pool.prepare(group)
                            continue
                    if pool.occupied.any():
                        pool.step()
                        pool.resolve_finished()
                        continue
                except (KeyboardInterrupt, SystemExit) as exc:
                    # fail the resident futures, then honor the interrupt —
                    # never swallow it into a served error
                    pool.fail_all(exc)
                    raise
                except BaseException as exc:
                    # last resort: tower/admission failures are isolated
                    # upstream (tower_down / prepare); anything landing here
                    # poisoned the resident state itself
                    pool.fail_all(exc)
                    continue
                # idle: no occupied slots, nothing admittable right now
                with self._q_cond:
                    if self._queue:
                        continue
                    if self._closed:
                        break
                    self._q_cond.wait(max(self.max_wait, 0.05))
        finally:
            self._tower_q.put(_STOP)

    def _tower_loop(self) -> None:
        while True:
            got = self._tower_q.get()
            if got is _STOP:
                break
            item, fut = got
            kind, payload, tag = item
            span = (self._span("serve.tower.drain", wave=tag,
                               rows=int(payload.size))
                    if kind == "drain"
                    else self._span("serve.tower.query_embed", group=tag))
            try:
                with span:
                    out = self._service_tower(item)
                fut.set_result(out)
            except (KeyboardInterrupt, SystemExit) as exc:
                fut.set_exception(exc)  # surface on drive, then honor it
                raise
            except BaseException as exc:  # surfaced on the drive thread
                fut.set_exception(exc)

    # --------------------------------------------------------------- rerank
    def _embed_queries(self, query_tokens: np.ndarray):
        """(B, S) tokens -> cheap (B, dim_d) on device, expensive (B, dim_D).

        Query-side embeddings are not charged to the quota: the budget counts
        *document* scorings (the paper's cost model)."""
        q_d = jnp.asarray(self.cheap.embed(query_tokens))
        q_D = jnp.asarray(self.expensive.embed(query_tokens))
        return q_d, q_D

    def rerank_query_batch(self, query_tokens: np.ndarray, *, quota: int,
                           k: int = 10,
                           ) -> tuple[np.ndarray, np.ndarray, list[ServeStats]]:
        """"Bi-metric (baseline)": top-quota by d, embed all with D, rerank."""
        if self.index_kind == "covertree":
            raise ValueError(
                "the rerank baseline needs the vamana proxy graph; "
                "build the engine with index='vamana'")
        b = query_tokens.shape[0]
        q_d, q_D = self._embed_queries(query_tokens)
        width = max(32, quota)
        res1 = self._stage1(q_d, width=width, pool=max(width, quota),
                            max_steps=8 * width)
        cand = np.asarray(res1.pool_ids[:, :quota])
        self._drain_tower(cand)
        doc_embs = self._emb_D[np.maximum(cand, 0)]  # host-side, no transfer
        diff = doc_embs - np.asarray(q_D)[:, None, :]
        dd = np.sqrt((diff * diff).sum(-1))
        dd = np.where(cand >= 0, dd, np.inf)
        order = np.argsort(dd, axis=1, kind="stable")[:, :k]
        d_calls = np.asarray(res1.n_calls)
        n_D = (cand >= 0).sum(1)
        stats = [ServeStats(d_calls=int(d_calls[i]), D_calls=int(n_D[i]))
                 for i in range(b)]
        return (np.take_along_axis(cand, order, 1).astype(np.int64),
                np.take_along_axis(dd, order, 1), stats)

    def rerank_query(self, query_tokens: np.ndarray, *, quota: int,
                     k: int = 10) -> tuple[np.ndarray, np.ndarray, ServeStats]:
        """One query (S,) tokens through the rerank baseline."""
        ids, dd, stats = self.rerank_query_batch(query_tokens[None],
                                                 quota=quota, k=k)
        ok = (ids[0] >= 0) & np.isfinite(dd[0])
        return ids[0][ok], dd[0][ok], stats[0]
