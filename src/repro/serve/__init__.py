"""Bi-metric serving: a persistent slot pool behind a request-centric API.

The engine (``repro.serve.engine.BiMetricEngine``) serves the paper's
two-tower deployment. The native request unit is a frozen ``SearchRequest``
(tokens, quota, k, n_seeds, expand_width, deadline_ms, priority); every
entry point — ``submit()``, ``query()``, ``query_batch()`` — accepts it,
and results come back as ``SearchResult`` (ids, D-dists, ``ServeStats``).
Legacy ``(tokens, quota=...)`` call forms still work through once-warning
deprecation shims.

The async drive is **continuous batching** over one resident slot pool
(the fixed-wave admission pipeline is retired):

* **admission** — ``submit()`` pushes requests onto a priority/deadline
  heap (higher ``priority`` first, FIFO within; ``deadline_ms`` expiry
  while queued fails the future with ``DeadlineExceeded``). The drive
  thread refills freed slots from the heap on *every* plan/commit step —
  not at wave boundaries — so a free lane never idles behind a running
  neighbor.
* **slot pool** — one resident ``(slots,)``-row search state
  (``repro.core.beam.BatchedSearchState``; inside the corpus mesh via
  ``ShardedStepper`` when ``shards > 1``). Admission recycles rows in
  place (``repro.core.beam.reset_slots``); static shapes (pool size,
  sorted-set capacity, seed/expand lane caps) grow monotonically in
  power-of-two buckets, each growth an exact semantic no-op.
* **mid-flight completion** — a slot that goes inactive resolves its
  future on that step and is immediately reusable; a long request never
  blocks its slot-mates (no head-of-line blocking).
* **tower overlap** — while the expensive tower drains a step's fresh
  documents, the drive thread runs the *next* admission group's
  cheap-tower embed + stage-1 search.

Per-row budget knobs (quota, beam width, step cap, seeds, expand width)
are operands in the core engine and the pools are streaming exact top-P
structures, so a slot row's answer is **bit-exact** vs the synchronous
``query_batch`` drive at any shard count — admission order, slot-mates and
capacity growth are invisible to it.

The stage-1 index is the ``index=`` knob: ``"vamana"`` (default — the
DiskANN instantiation, greedy beam search over the proxy-built graph) or
``"covertree"`` (the Theorem B.3 instantiation — per-level cover-tree
descent, paper Algorithm 3, driven through the same slot pool as chunked
``plan_step``/``commit_scores`` waves with the memoized D-call set living
in the slot's ``ScoredSet``). Cover-tree rows ignore ``n_seeds`` /
``expand_width`` (the tree's root cover and fanout take their place),
``covertree_eps`` / ``covertree_T`` tune the descent's stopping rule and
the offline build scale, and ``rerank_query_batch`` is vamana-only. Both
index kinds serve bit-exact vs their synchronous ``query_batch`` drive.

Observability: ``ServeStats`` splits per-request latency into ``queue_ms``
(submit → slot admission) + ``compute_ms`` (admission → resolve), with
``latency_ms`` their sum, plus admission-time ``slot_occupancy`` /
``queue_depth`` snapshots; ``BiMetricEngine.counters()`` exposes the
cumulative ``EngineCounters``: submitted / admitted / completed /
cancelled / deadline_misses and instantaneous depth/occupancy; the slot
drive's stage-2 ``waves`` and the ``doc_lookups`` they charged; the
expensive tower's ``drained_rows`` and ``drain_batches``; each tower's
rows computed and rows holding a token; and, per ``serve.*`` span, how
many closed (``span_n``) and their host seconds (``span_s``). The slot
drive opens those spans (admission, cheap embed, stage 1, the query-embed
wait, each wave with its plan, drain wait, gather and commit, resolution;
the tower lane's drains and query embeds) as ``jax.profiler``
host spans, so a profiler trace puts them on the device's clock.
``close()`` cancels still-queued requests (``CancelledError``) instead of
flushing them; admitted slots still resolve. The device-side kernel route
is the ``backend=`` knob (``repro.kernels``).

Failure semantics
-----------------
The contract is **failures are scoped to requests, never to the engine**,
with four nested isolation domains (async path):

* **one request** — malformed input (bad token shape) fails only that
  request's future at admission.
* **one admission group** — a cheap-tower or stage-1 error while staging a
  group fails that group's futures with ``AdmissionFailed`` (the original
  exception on ``__cause__``); resident slots never notice.
* **the tower lane** — an expensive-tower failure (query embed or document
  drain) is retried up to ``tower_retries`` times with exponential backoff
  starting at ``retry_backoff_ms`` (transient errors only: an exception
  carrying ``transient=False`` — or a ``TowerTimeout``, a call that blew
  ``drain_timeout_ms`` — is never retried inline). A retried drain is
  idempotent: the document cache is written only after a successful
  forward pass, so recovered runs are **bit-exact** vs fault-free runs.
  When the lane gives up, the ``on_tower_failure`` policy decides the
  affected residents' fate — ``"fail"`` (default) fails each future with
  ``TowerFailure`` chaining the original traceback; ``"degrade"`` resolves
  each with its stage-1 proxy ranking, ``ServeStats.degraded=True``.
  Either way the engine keeps serving. ``breaker_threshold`` consecutive
  failures open a circuit breaker for ``breaker_cooldown_ms`` (then
  half-open probes): while open, tower calls are refused without being
  attempted — under ``"degrade"`` the engine serves proxy-only without
  occupying slots; under ``"fail"`` requests shed fast with
  ``TowerFailure``.
* **the engine** — only an error *outside* those domains (poisoned
  resident device state) reaches ``fail_all``: every resident + staged
  future fails with ``EngineFailure`` (original on ``__cause__``), the
  resident state is dropped, and the next admission re-initializes it.
  ``KeyboardInterrupt`` / ``SystemExit`` fail the residents and then
  re-raise — they are never converted into a served error.

``deadline_ms`` is enforced at three points: queued expiry and
admission-pop expiry fail the future with ``DeadlineExceeded`` (the
request never ran, so there is nothing to degrade to), and **mid-flight**
expiry — checked every drive iteration and every 20 ms inside a tower
wait when deadlines are resident — follows ``on_tower_failure``:
``"degrade"`` resolves the slot with its proxy ranking (counted in both
``deadline_misses`` and ``degraded``), ``"fail"`` raises
``DeadlineExceeded``. Expired rows close their frontier in place
(``repro.core.beam.early_resolve``); co-resident rows are untouched
bit-for-bit.

**Degraded-result guarantee.** A degraded result is the stage-1 proxy
ranking under the cheap metric ``d``. The paper's premise (arXiv
2406.02891) is that ``d`` is a C-approximation of the ground-truth metric
``D`` — ``D(x,y)/C <= d(x,y) <= C·D(x,y)`` — so proxy-only answers carry
the same bounded quality loss the bi-metric framework's stage 1 does:
every returned id is within ``C²`` of optimal under ``D``. Degradation is
the paper's accuracy/efficiency knob repurposed as an operational
fallback, and ``degraded=True`` marks exactly which answers took it
(cover-tree rows have no proxy stage; they degrade to their current
D-scored pool prefix mid-flight, and shed fast when the breaker is open).

Fault injection for tests/benchmarks is ``repro.serve.faults.FaultPlan``
(seeded, deterministic, threaded through ``BiMetricEngine(faults=...)``);
``BiMetricEngine.health()`` snapshots breaker state + counters.
"""
from repro.serve.engine import (AdmissionFailed,  # noqa: F401
                                BiMetricEngine, DeadlineExceeded, EmbedTower,
                                EngineCounters, EngineFailure, SearchRequest,
                                SearchResult, ServeFuture, ServeStats,
                                TowerFailure, TowerTimeout)
from repro.serve.faults import (CircuitBreaker,  # noqa: F401
                                FaultPlan, FaultSpec, InjectedFault)
