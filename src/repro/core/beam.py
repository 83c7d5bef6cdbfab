"""Batched greedy graph search (paper Algorithm 1) — the one shared hot loop.

Every search in the system — index construction (metric d), stage-1 search
(d), stage-2 search (D), the single-metric baseline, and the serving engine —
runs this engine. One step processes a whole batch of ``B`` queries in a
single fixed-shape update:

* each query's frontier is a fixed-size *pool* of its best ``pool_size``
  scored vertices (sorted by distance); the classic beam is the length-``L``
  prefix;
* one step = pick up to ``expand_width`` best unexpanded vertices in each
  query's beam prefix, gather the ``(B, E, R)`` neighbor fanout, drop
  already-scored vertices against the per-query scored bitmap, score the
  survivors with one batched distance call, and merge (beam ‖ fanout) back
  into the pools in one call (``repro.kernels.ops.merge_pool_batch`` — the
  stable jnp merge off-TPU, the fused Pallas bitonic kernel on TPU);
* per-query *dedup state* provides exact dedup — a vertex's distance is
  computed at most once per step wave, so counting scored candidates counts
  distance-function *calls* exactly (the paper's cost model). Two backends
  implement it behind ``_scored_lookup`` / ``_scored_scatter``:

  - ``bitmap`` — the dense (B, N) bool bitmap: O(1) lookup/scatter per
    lane, O(B·N) state. The only choice when the call budget is unbounded
    (graph construction, stage-1 proxy search).
  - ``sorted`` — a :class:`ScoredSet`: per-query **ascending id arrays of
    static capacity C = quota** (+ a count), lookup via ``searchsorted``,
    insertion via the same tie-stable top-k merge as the pools
    (``repro.kernels.ops.sorted_set_merge``). The bi-metric quota guarantee
    — one insertion per counted distance call, ``n_calls <= quota`` — means
    the set never overflows, so quota-bounded searches carry
    O(B·quota) dedup state instead of O(B·N) (NMSLIB's visited-set trick,
    sized to the budget rather than the corpus).

  ``dedup="auto"`` (the default) is drive-shape aware — see
  :func:`resolve_dedup`: host-driven dispatch loops (the serving engine's
  stage 2, where the non-donated bitmap would be copied every step) pick
  ``sorted`` exactly when the quota bound is static and smaller than the
  corpus; fused ``while_loop`` programs keep the bitmap (XLA aliases the
  carry, so on CPU the bitmap's step cost is O(wave) regardless of N —
  force ``dedup="sorted"`` when the bitmap's *memory* is the problem;
  note the fused entry points still materialize the (B, N) bitmap once at
  loop exit for ``SearchResult.scored``, so when even that single
  allocation is too large, drive :func:`init_state` / :func:`plan_step` /
  :func:`commit_scores` directly, as the serving engine does — that path
  never materializes it).
  Both backends are **bit-exact** to each other: same pool ids/dists,
  ``n_calls``, ``n_steps`` and scored set (the sorted backend materializes
  the equivalent bitmap once, after the loop, for :class:`SearchResult`);
* an explicit ``quota`` bounds the number of distance calls per query:
  candidates that would exceed the quota are masked out (never scored, never
  used), so the search is *exactly* budget-feasible per query, not just in
  expectation. Queries whose quota or frontier is exhausted freeze in place
  while the rest of the batch keeps stepping.

With ``expand_width=1`` a batched search is bit-exact to running each query
alone (and to the historical per-query engine): same pool ids, distances and
call counts. ``expand_width>1`` is the throughput knob — it cuts the step
count roughly E-fold at the cost of a slightly greedier expansion order (the
standard batched relaxation used by GPU graph-ANN engines); each wave's
fanout is positionally deduped, so a vertex reachable from two same-wave
frontier vertices is still paid for exactly once. (At E=1 the historical
behavior is preserved bit-exactly, including its quirk of scoring duplicate
ids inside one adjacency row twice.)

The step is exposed as ``plan_step`` / ``commit_scores`` so callers that
cannot score inside a ``while_loop`` (the serving engine, whose expensive
metric is a lazily-evaluated model forward pass) drive the identical loop
from the host: plan on device, score through the tower, commit on device.

The same plan/commit wave runs **device-parallel** over a corpus mesh
(:func:`sharded_greedy_search`): each device owns a contiguous corpus block,
waves are scored by a psum of shard-local fused gathers, and the pools stay
replicated — every device runs the identical merge on the identical
replicated wave, so the sharded engine is *bit-exact* vs the unsharded one
(pool ids/dists, n_calls and the scored set). The dedup backends shard
differently: the ``bitmap`` is **column-sharded** — each device holds the
(B, N/shards) slice of the columns it owns, lookups psum-OR the owner's
answer, scatters land on the owner only — while the ``sorted`` set is
**replicated like the pools** — (B, quota) per device, every membership op
collective-free. That is the memory trade: column-sharding divides the
O(B·N) bitmap across the mesh but pays a collective per lookup; the
replicated set costs O(B·quota) per device (independent of N *and* of the
shard count) and removes the dedup collective from the wave entirely.
``ShardCtx`` is the per-step handle; the collectives live in
``repro.distributed.collectives``.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.kernels import backend as kernel_backend
from repro.kernels import ops

Array = jax.Array

NO_QUOTA = jnp.iinfo(jnp.int32).max // 2


class ShardCtx(NamedTuple):
    """Handle for running the engine inside a ``shard_map`` over a corpus mesh.

    ``axis_name`` is the mesh axis the corpus (and the scored bitmap's column
    dim) is sharded over; ``n_local`` is the contiguous block of corpus rows
    each device owns (global rows ``[axis_index * n_local, ...)``). When a
    ``ShardCtx`` is passed, ``BatchedSearchState.scored`` is the *local*
    (B, n_local) column slice; everything else in the state is replicated.
    """

    axis_name: str
    n_local: int


class ScoredSet(NamedTuple):
    """Quota-proportional dedup state: per-query sorted membership arrays.

    ``ids`` (B, C) int32 ascending with ``repro.kernels.ops.SET_PAD``
    padding; the static capacity C must be >= every per-query quota, so the
    engine's exact quota accounting (one insertion per counted call,
    ``n_calls <= quota``) guarantees no entry is ever dropped. ``count``
    (B,) is the set's occupancy — insertions so far, i.e. ``n_calls``
    minus any ``calls_init``; the search itself never branches on it (the
    quota mask already bounds insertions), it exists as the overflow
    diagnostic: ``count <= capacity`` must hold at every step. Duplicate
    ids inside one E=1 adjacency row occupy one slot each, exactly
    mirroring their ``n_calls`` cost. C = 0 is a valid zero-capacity set
    (quota-0 rows): every op degenerates to a no-op.

    Under a :class:`ShardCtx` the set is **replicated** across the shard
    axis, like the pools — membership ops are collective-free.
    """

    ids: Array  # (B, C) int32 ascending; SET_PAD padded
    count: Array  # (B,) int32 insertions so far

    @property
    def capacity(self) -> int:
        return self.ids.shape[1]


def empty_scored_set(batch: int, capacity: int) -> ScoredSet:
    return ScoredSet(
        ids=jnp.full((batch, capacity), ops.SET_PAD, jnp.int32),
        count=jnp.zeros((batch,), jnp.int32),
    )


class BatchedSearchState(NamedTuple):
    """Per-query search state, batch-leading. All shapes are static.

    ``scored`` is the dedup state: the dense (B, N) bool bitmap *or* a
    :class:`ScoredSet` (the quota-proportional sorted backend) — every
    consumer dispatches on the type. Under a :class:`ShardCtx` the bitmap
    form is the device-local (B, n_local) column slice of the global (B, N)
    bitmap while the sorted form stays replicated; all other fields are
    replicated across the shard axis (the replicated-pool invariant).
    """

    pool_ids: Array  # (B, P) int32, sorted by dist; -1 pad
    pool_dists: Array  # (B, P) f32; +inf pad
    expanded: Array  # (B, P) bool
    scored: Array | ScoredSet  # dedup state + exact call counting
    n_calls: Array  # (B,) int32
    n_steps: Array  # (B,) int32


class SearchResult(NamedTuple):
    pool_ids: Array
    pool_dists: Array
    scored: Array
    n_calls: Array
    n_steps: Array


def _positional_dedup(ids: Array) -> Array:
    """Per row: an id equal to an earlier id in the row becomes -1."""
    e = ids.shape[-1]
    dup = (ids[..., :, None] == ids[..., None, :]) & (
        jnp.arange(e)[:, None] > jnp.arange(e)[None, :]
    )
    return jnp.where(dup.any(axis=-1), -1, ids)


def _static_quota_bound(quota) -> int | None:
    """max(quota) as a static int, or None when quota is a traced value.

    Python ints, numpy scalars/arrays and *concrete* jax arrays all have a
    static bound; a tracer (e.g. a jitted operand) does not — note that
    merely wrapping a constant in ``jnp`` ops inside a trace stages it, so
    the tracer check must come before any conversion.
    """
    if isinstance(quota, jax.core.Tracer):
        return None
    return int(np.max(np.asarray(quota)))


def resolve_dedup(
    dedup: str,
    set_capacity: int | None,
    quota,
    n_points: int,
    scored_init=None,
    *,
    drive: str = "host",
) -> tuple[str, int | None]:
    """Pick the dedup backend -> ``("bitmap", None) | ("sorted", capacity)``.

    ``"auto"`` selects ``sorted`` exactly when the quota bound is *static*
    (concrete at trace time) and smaller than the corpus — the regime where
    O(quota) membership state beats the O(N) bitmap; a traced quota (no
    static bound), an unbounded quota, or a continued bitmap
    (``scored_init``) falls back to ``bitmap``. An explicit backend is
    honored as given; ``sorted`` derives its capacity from the static quota
    bound when ``set_capacity`` is None.

    ``drive`` qualifies the auto rule by loop shape. ``"host"`` (the
    serving engine's dispatch-per-step stage 2) applies the rule above: the
    non-donated bitmap is round-tripped through every dispatch, so
    quota-proportional state wins by the corpus/quota ratio — ~9x at
    quota 256 on a 1M-row corpus (the gated BENCH_search_perf dedup
    scenario). ``"fused"`` (one jitted
    ``while_loop`` — :func:`batched_greedy_search` and the stage-1 /
    bi-metric paths) keeps the bitmap on auto: XLA aliases the loop carry,
    making the bitmap's per-step cost O(wave) regardless of N, and the
    sorted merge measures slower there on CPU at every N that fits memory
    (recorded in the same bench scenario). Explicit ``dedup="sorted"``
    still opts a fused loop into O(quota) state — the right call when the
    bitmap itself is the memory problem (huge N × batch, or accelerator
    HBM budgets).
    """
    if dedup == "bitmap":
        return "bitmap", None
    if dedup == "auto" and drive == "fused" and not isinstance(
            scored_init, ScoredSet):
        return "bitmap", None
    if scored_init is not None and not isinstance(scored_init, ScoredSet):
        if dedup == "sorted":
            raise ValueError(
                "dedup='sorted' cannot continue a bitmap scored_init")
        return "bitmap", None
    if isinstance(scored_init, ScoredSet):
        return "sorted", scored_init.capacity
    if dedup not in ("sorted", "auto"):
        raise ValueError(f"unknown dedup backend {dedup!r}")
    qmax = _static_quota_bound(quota)
    if set_capacity is None:
        if qmax is None:
            if dedup == "sorted":
                raise ValueError(
                    "dedup='sorted' with a traced quota needs an explicit "
                    "static set_capacity")
            return "bitmap", None  # auto: no static quota bound -> bitmap
        set_capacity = qmax
    elif qmax is not None and qmax <= NO_QUOTA // 2 and set_capacity < qmax:
        # an undersized set would silently drop scored ids (dedup holes)
        raise ValueError(f"set_capacity={set_capacity} < quota bound {qmax}")
    set_capacity = max(int(set_capacity), 0)
    if dedup == "auto" and set_capacity >= n_points:
        return "bitmap", None  # the bitmap is the smaller structure
    return "sorted", set_capacity


def scored_set_to_bitmap(sset: ScoredSet, n_points: int) -> Array:
    """Materialize the (B, N) bool bitmap a ScoredSet is equivalent to.

    One scatter outside the hot loop — used to keep ``SearchResult.scored``
    backend-independent (bit-identical across backends).
    """
    b, c = sset.ids.shape
    bitmap = jnp.zeros((b, n_points), dtype=bool)
    if c == 0:
        return bitmap
    rows = jnp.arange(b)[:, None]
    valid = sset.ids != ops.SET_PAD
    # pads clip onto column n-1 with valid=False, so .max() is a no-op there
    return bitmap.at[rows, jnp.clip(sset.ids, 0, n_points - 1)].max(valid)


def _scored_lookup(
    scored: Array | ScoredSet, ids: Array, shard: ShardCtx | None
) -> Array:
    """(B, K) bool: which (valid) ids are already in the dedup state."""
    if isinstance(scored, ScoredSet):
        if shard is None:
            return ops.sorted_set_lookup(scored.ids, ids)
        from repro.distributed import collectives

        return collectives.member_lookup(
            scored.ids, ids, axis_name=shard.axis_name)
    if shard is None:
        return (ids >= 0) & jnp.take_along_axis(
            scored, jnp.maximum(ids, 0), axis=1
        )
    from repro.distributed import collectives

    return collectives.bitmap_lookup(scored, ids, axis_name=shard.axis_name)


def _scored_scatter(
    scored: Array | ScoredSet, ids: Array, mark: Array,
    shard: ShardCtx | None,
) -> Array | ScoredSet:
    """Mark the kept lanes' ids in the dedup state (backend dispatch)."""
    if isinstance(scored, ScoredSet):
        if shard is None:
            merged = ops.sorted_set_merge(
                scored.ids, jnp.where(mark, ids, ops.SET_PAD))
        else:
            from repro.distributed import collectives

            merged = collectives.member_insert(
                scored.ids, ids, mark, axis_name=shard.axis_name)
        return ScoredSet(
            ids=merged,
            count=scored.count + mark.sum(axis=1, dtype=jnp.int32))
    if shard is None:
        rows = jnp.arange(ids.shape[0])[:, None]
        # scatter-OR (max): padding ids all alias index 0, so a plain set()
        # races
        return scored.at[rows, jnp.maximum(ids, 0)].max(mark)
    from repro.distributed import collectives

    return collectives.bitmap_scatter(scored, ids, mark,
                                      axis_name=shard.axis_name)


def init_state(
    entry_ids: Array,
    *,
    n_points: int,
    pool_size: int,
    quota: Array,
    scored_init: Array | ScoredSet | None = None,
    calls_init: Array | int = 0,
    shard: ShardCtx | None = None,
    dedup: str = "bitmap",
    set_capacity: int | None = None,
) -> tuple[BatchedSearchState, Array, Array]:
    """Empty pools + the entry wave, quota-masked but not yet scored.

    Returns ``(state, safe_entries (B, E0), keep (B, E0))``; the caller scores
    ``safe_entries`` (ids < 0 are masked) and feeds the result to
    :func:`commit_scores`. ``scored`` / ``n_calls`` already account for the
    kept entries — a wave is paid for when it is planned.

    ``dedup`` selects the dedup backend *concretely* (``"bitmap"`` or
    ``"sorted"`` — resolve ``"auto"`` first via :func:`resolve_dedup`);
    ``set_capacity`` is the sorted backend's static capacity (>= the max
    quota; 0 is a valid zero-capacity set for all-quota-0 batches). Under a
    :class:`ShardCtx` the bitmap is allocated as the device-local
    (B, n_local) column slice (entry marks land on their owning shard)
    while the sorted set is replicated.
    """
    b, e = entry_ids.shape
    entry_ids = _positional_dedup(entry_ids.astype(jnp.int32))
    valid = entry_ids >= 0
    order_idx = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    quota = jnp.broadcast_to(jnp.asarray(quota, jnp.int32), (b,))
    calls0 = jnp.broadcast_to(jnp.asarray(calls_init, jnp.int32), (b,))
    keep = valid & (order_idx < (quota - calls0)[:, None])
    safe = jnp.where(keep, entry_ids, -1)

    if scored_init is not None:
        scored = scored_init
    elif dedup == "sorted":
        if set_capacity is None:
            raise ValueError("dedup='sorted' needs a static set_capacity")
        scored = empty_scored_set(b, int(set_capacity))
    else:
        n_cols = n_points if shard is None else shard.n_local
        scored = jnp.zeros((b, n_cols), dtype=bool)
    scored = _scored_scatter(scored, safe, keep, shard)
    n_calls = calls0 + keep.sum(axis=1, dtype=jnp.int32)

    p = pool_size
    state = BatchedSearchState(
        pool_ids=jnp.full((b, p), -1, jnp.int32),
        pool_dists=jnp.full((b, p), jnp.inf, jnp.float32),
        expanded=jnp.zeros((b, p), dtype=bool),
        scored=scored,
        n_calls=n_calls,
        n_steps=jnp.zeros((b,), jnp.int32),
    )
    return state, safe, keep


def _per_query(v: int | Array, b: int) -> Array:
    """Broadcast a scalar-or-(B,) knob to a (B,) int32 vector."""
    return jnp.broadcast_to(jnp.asarray(v, jnp.int32), (b,))


def reset_slots(
    state: BatchedSearchState,
    reset: Array,
    entry_ids: Array,
    quota: Array,
    *,
    shard: ShardCtx | None = None,
) -> tuple[BatchedSearchState, Array, Array]:
    """Re-initialize the rows in ``reset`` to a fresh entry wave, in place.

    The slot-pool admission primitive: ``reset`` (B,) bool marks the rows
    (slots) being recycled for newly admitted queries; their pools, dedup
    state and counters are cleared and re-seeded from ``entry_ids`` exactly
    as :func:`init_state` would — positional entry dedup, quota-masked keep,
    scored/n_calls pre-paid at plan time. Rows outside ``reset`` are
    untouched bit-for-bit (their lanes in the returned ``safe`` are -1, so
    the follow-up entry :func:`commit_scores` is an exact no-op on them).

    Returns ``(state', safe (B, E0), keep (B, E0))``; the caller scores
    ``safe`` and commits, same contract as :func:`init_state`. Under a
    :class:`ShardCtx` the bitmap rows are cleared on every shard's local
    column slice and the entry marks land on their owners, so a recycled
    slot's dedup state is indistinguishable from a freshly initialized one.
    """
    b, p = state.pool_ids.shape
    reset = jnp.broadcast_to(jnp.asarray(reset, bool), (b,))
    entry_ids = _positional_dedup(entry_ids.astype(jnp.int32))
    valid = entry_ids >= 0
    order_idx = jnp.cumsum(valid.astype(jnp.int32), axis=1) - 1
    quota = _per_query(quota, b)
    keep = valid & (order_idx < quota[:, None]) & reset[:, None]
    safe = jnp.where(keep, entry_ids, -1)

    scored = state.scored
    if isinstance(scored, ScoredSet):
        scored = ScoredSet(
            ids=jnp.where(reset[:, None], ops.SET_PAD, scored.ids),
            count=jnp.where(reset, 0, scored.count),
        )
    else:
        scored = jnp.where(reset[:, None], False, scored)
    scored = _scored_scatter(scored, safe, keep, shard)

    rm = reset[:, None]
    state = BatchedSearchState(
        pool_ids=jnp.where(rm, -1, state.pool_ids),
        pool_dists=jnp.where(rm, jnp.inf, state.pool_dists),
        expanded=jnp.where(rm, False, state.expanded),
        scored=scored,
        n_calls=jnp.where(
            reset, keep.sum(axis=1, dtype=jnp.int32), state.n_calls),
        n_steps=jnp.where(reset, 0, state.n_steps),
    )
    return state, safe, keep


def grow_state(
    state: BatchedSearchState,
    *,
    pool_size: int | None = None,
    set_capacity: int | None = None,
) -> BatchedSearchState:
    """Right-pad a state's static shapes — an exact semantic no-op.

    The slot pool grows its resident state monotonically when an admitted
    request needs a larger pool (P) or sorted-set capacity (C) than any
    before it. Both growths are provably invisible to the search: pools are
    streaming exact top-P structures, so appended (-1, +inf, unexpanded)
    lanes never alter the surviving prefix (P-invariance), and
    ``ops.SET_PAD`` sorts to the tail of each ascending ScoredSet row, so
    appended pad slots leave every lookup/merge result unchanged. Shrinking
    is not supported (it could drop live entries); passing a smaller value
    keeps the current shape.
    """
    pool_ids, pool_dists, expanded = (
        state.pool_ids, state.pool_dists, state.expanded)
    p = pool_ids.shape[1]
    if pool_size is not None and pool_size > p:
        pad = ((0, 0), (0, pool_size - p))
        pool_ids = jnp.pad(pool_ids, pad, constant_values=-1)
        pool_dists = jnp.pad(pool_dists, pad, constant_values=jnp.inf)
        expanded = jnp.pad(expanded, pad, constant_values=False)
    scored = state.scored
    if (isinstance(scored, ScoredSet) and set_capacity is not None
            and set_capacity > scored.capacity):
        scored = ScoredSet(
            ids=jnp.pad(
                scored.ids,
                ((0, 0), (0, set_capacity - scored.capacity)),
                constant_values=ops.SET_PAD),
            count=scored.count,
        )
    return state._replace(
        pool_ids=pool_ids, pool_dists=pool_dists, expanded=expanded,
        scored=scored)


def active_mask(
    state: BatchedSearchState,
    *,
    beam_width: int | Array,
    quota: Array,
    max_steps: int | Array,
) -> Array:
    """(B,) — which queries still have an open frontier, budget and steps.

    ``beam_width`` and ``max_steps`` may be scalars or per-query (B,)
    vectors — mixed-configuration batches (the serving engine's request
    waves) give every query *its own* beam prefix and step cap, so a query
    behaves bit-exactly as if it ran alone regardless of its wave-mates.
    """
    b, p = state.pool_ids.shape
    L = _per_query(beam_width, b)
    in_beam = jnp.arange(p)[None, :] < L[:, None]
    frontier = (~state.expanded) & jnp.isfinite(state.pool_dists) & in_beam
    quota = _per_query(quota, b)
    steps = _per_query(max_steps, b)
    return (
        frontier.any(axis=1)
        & (state.n_calls < quota)
        & (state.n_steps < steps)
    )


def reset_expanded(state: BatchedSearchState, rows: Array) -> BatchedSearchState:
    """Re-open the frontier on the masked ``rows`` (clear ``expanded``).

    The graph descent never revisits a vertex, but the cover-tree level
    descent expands the *same* surviving centers again at the next (finer)
    level — between levels the driver clears the expanded flags so
    :func:`plan_step`'s frontier selection sees the whole pool prefix
    afresh. ``rows`` is a (B,) bool mask (or scalar); pools, scores,
    dedup state and call counters are untouched, so re-expansion stays an
    exact no-op for already-memoized ids.
    """
    b = state.pool_ids.shape[0]
    rows = jnp.broadcast_to(jnp.asarray(rows, bool), (b,))
    return state._replace(
        expanded=jnp.where(rows[:, None], False, state.expanded)
    )


def early_resolve(state: BatchedSearchState, rows: Array) -> BatchedSearchState:
    """Close the frontier on the masked ``rows`` — the inverse of
    :func:`reset_expanded`: every pool lane is marked expanded, so
    :func:`active_mask` reports the row inactive regardless of its
    remaining quota/step budget.

    This is the serving layer's graceful-degradation primitive: a slot
    being resolved early (mid-flight deadline expiry, or proxy-only
    results while the expensive tower is open-circuit) is frozen in the
    resident state so no later plan re-expands it and no level-descent
    ``reset_expanded`` can resurrect it. Pools, scores, dedup state and
    call counters are untouched — the already-scored pool prefix stays
    readable for the degraded answer — and non-masked rows pass through
    bit-for-bit. ``rows`` is a (B,) bool mask (or scalar).
    """
    b = state.pool_ids.shape[0]
    rows = jnp.broadcast_to(jnp.asarray(rows, bool), (b,))
    return state._replace(
        expanded=jnp.where(rows[:, None], True, state.expanded)
    )


def plan_step(
    state: BatchedSearchState,
    adjacency: Array,
    *,
    beam_width: int | Array,
    quota: Array,
    max_steps: int | Array,
    expand_width: int | Array = 1,
    expand_cap: int | None = None,
    shard: ShardCtx | None = None,
    level: Array | None = None,
    wave_dedup: bool = True,
) -> tuple[BatchedSearchState, Array, Array, Array]:
    """One expansion wave: pick frontiers, gather fanout, mask to the quota.

    Returns ``(state', safe (B, E*R), keep (B, E*R), active (B,))`` where
    ``state'`` has ``expanded`` / ``scored`` / ``n_calls`` / ``n_steps``
    advanced (a wave is paid for when planned). The caller scores ``safe``
    and calls :func:`commit_scores`. Frozen (inactive) queries plan an
    all-masked wave, which commits as an exact no-op.

    ``expand_width`` may be a scalar or a per-query (B,) vector (the slot
    pool's mixed-request batches); the wave's static lane count E is
    ``expand_cap`` when given (required when the vector is traced),
    otherwise the concrete max. A row with expand_width 1 keeps the
    historical E=1 semantics bit-exactly — including its quirk of paying
    for duplicate ids inside one adjacency row twice — regardless of its
    batch-mates' widths.

    ``level`` (a per-query (B,) int vector) switches the fanout table from
    a flat graph ``(N, R)`` to a level-stacked ``(L, N, R)`` one —
    ``adjacency[level[b], vertex]`` — which is how the cover-tree descent
    steps co-resident queries sitting at *different* tree levels in one
    program. ``wave_dedup=False`` skips the O((E·R)²) same-wave positional
    dedup; only safe when the expanded rows' fanouts are disjoint by
    construction (cover-tree child slabs partition the next level).

    Under a :class:`ShardCtx`, the already-scored lookup OR-reduces the
    owning shard's bitmap slice across the axis and the scatter lands only
    on the owner; all other planning math runs on replicated inputs, so the
    planned wave is replicated (and bit-exact vs the unsharded plan).
    """
    b, p = state.pool_ids.shape
    L = _per_query(beam_width, b)
    if expand_cap is None:
        expand_cap = _static_quota_bound(expand_width)
        if expand_cap is None:
            raise ValueError(
                "a traced (B,) expand_width needs a static expand_cap")
    E = max(int(expand_cap), 1)
    ew = _per_query(expand_width, b)
    r = adjacency.shape[-1]
    quota = jnp.broadcast_to(jnp.asarray(quota, jnp.int32), (b,))

    active = active_mask(
        state, beam_width=L, quota=quota, max_steps=max_steps
    )
    # best unexpanded slots in the beam prefix (pool sorted -> first open)
    open_ = (
        (~state.expanded)
        & jnp.isfinite(state.pool_dists)
        & (jnp.arange(p)[None, :] < L[:, None])
    )
    rank = jnp.cumsum(open_.astype(jnp.int32), axis=1) - 1
    sel = open_ & (rank < ew[:, None]) & active[:, None]
    expanded = state.expanded | sel
    # slot positions of the selected vertices, in pool order; p == "none"
    # (top_k of the negated positions == first-E ascending, without a sort)
    slot_pos = -jax.lax.top_k(
        jnp.where(sel, -jnp.arange(p)[None, :], -p), E
    )[0]
    has = slot_pos < p
    verts = jnp.where(
        has,
        jnp.take_along_axis(state.pool_ids, jnp.minimum(slot_pos, p - 1), 1),
        -1,
    )

    adj = adjacency.astype(jnp.int32)
    if level is None:
        nbrs = adj[jnp.maximum(verts, 0)]  # (B, E, R)
    else:
        lev = _per_query(level, b)
        nbrs = adj[lev[:, None], jnp.maximum(verts, 0)]  # (B, E, R)
    nbrs = jnp.where((verts >= 0)[:, :, None], nbrs, -1)
    cand = nbrs.reshape(b, E * r)
    if E > 1 and wave_dedup:
        # a vertex reachable from two same-wave frontier vertices must be
        # paid for once; a row at expand_width 1 keeps the historical
        # behavior bit-exactly (which scores duplicate ids inside one
        # adjacency row twice), even when its batch-mates run wider.
        cand = jnp.where((ew > 1)[:, None], _positional_dedup(cand), cand)
    fresh = (cand >= 0) & ~_scored_lookup(state.scored, cand, shard)
    # exact quota masking: only the first `remaining` fresh ids get scored
    call_idx = jnp.cumsum(fresh.astype(jnp.int32), axis=1) - 1
    keep = fresh & (call_idx < (quota - state.n_calls)[:, None])
    safe = jnp.where(keep, cand, -1)

    scored = _scored_scatter(state.scored, safe, keep, shard)
    n_calls = state.n_calls + keep.sum(axis=1, dtype=jnp.int32)
    n_steps = state.n_steps + active.astype(jnp.int32)
    state = state._replace(
        expanded=expanded, scored=scored, n_calls=n_calls, n_steps=n_steps
    )
    return state, safe, keep, active


def commit_scores(
    state: BatchedSearchState,
    safe: Array,
    keep: Array,
    dists: Array,
    *,
    backend: str | kernel_backend.Backend | None = None,
    use_fused_merge: bool | None = None,
    interpret: bool | None = None,
) -> BatchedSearchState:
    """Merge a scored wave into the pools (masked lanes are +inf no-ops).

    ``backend`` selects the merge route (``"pallas"`` = the lane-padded
    bitonic kernel; everything else = the stable XLA merge); the legacy
    ``use_fused_merge`` / ``interpret`` kwargs remain as deprecated shims.
    """
    be = kernel_backend.resolve_backend(
        backend, use_fused_merge=use_fused_merge, interpret=interpret,
        _caller="beam.commit_scores")
    d = jnp.where(keep, dists.astype(jnp.float32), jnp.inf)
    pool_ids, pool_dists, expanded = ops.merge_pool_batch(
        state.pool_ids,
        state.pool_dists,
        state.expanded,
        safe,
        d,
        backend=be,
    )
    return state._replace(
        pool_ids=pool_ids, pool_dists=pool_dists, expanded=expanded
    )


def batched_greedy_search(
    dist_fn_batch: Callable[[Array, Array], Array],
    adjacency: Array,
    query_ctx: Array | None,
    entry_ids: Array,
    *,
    n_points: int,
    beam_width: int | Array,
    pool_size: int | None = None,
    quota: int | Array = NO_QUOTA,
    expand_width: int = 1,
    max_steps: int | Array | None = None,
    scored_init: Array | ScoredSet | None = None,
    calls_init: Array | int = 0,
    backend: str | kernel_backend.Backend | None = None,
    use_fused_merge: bool | None = None,
    interpret: bool | None = None,
    shard: ShardCtx | None = None,
    dedup: str = "auto",
    set_capacity: int | None = None,
) -> SearchResult:
    """Greedy beam search over ``adjacency`` for a whole query batch.

    Args:
      dist_fn_batch: maps ``(query_ctx, ids (B, K) int32) -> (B, K) f32``
        distances; ids < 0 must map to +inf. Every *finite* evaluation is one
        metric call. ``repro.core.distances.EmbeddingMetric.dists_batch`` and
        the fused ``repro.kernels.ops.gather_score`` both satisfy this.
      adjacency: (N, R) int32 out-neighbors, -1 padded.
      query_ctx: opaque per-query context forwarded to ``dist_fn_batch``
        (usually the (B, dim) query embeddings; may be None).
      entry_ids: (B, E0) int32 starting vertices (deduped here; -1 pads ok).
      n_points: N (for the scored bitmap).
      beam_width: L — expansion happens within the best-L prefix. Scalar or
        (B,) for mixed per-query widths (a (B,) beam width requires an
        explicit static ``pool_size``).
      pool_size: P >= L — how many best-scored vertices to retain.
      quota: max distance calls per query (incl. entry scoring); scalar or
        (B,) for mixed per-query budgets.
      expand_width: E — frontier vertices expanded per query per step. 1 is
        bit-exact to the per-query engine; >1 trades exact expansion order
        for ~E-fold fewer steps.
      max_steps: cap on per-query expansions (defaults to a safe bound);
        scalar or (B,) for mixed per-query caps.
      scored_init / calls_init: continue an earlier search's accounting —
        used by the bi-metric stage-2 search (see bimetric.py).
      backend: merge-route selection (``repro.kernels.resolve_backend``
        values — ``"pallas"`` runs the lane-padded bitonic pool merge, the
        default keeps the stable XLA merge). Distance scoring is the
        caller's ``dist_fn_batch``, so its backend is chosen where that
        closure is built (:func:`fused_dist_fn`).
      use_fused_merge / interpret: deprecated shims for ``backend``.
      shard: run the loop device-parallel inside a ``shard_map`` over a
        corpus mesh — ``dist_fn_batch`` must then be the wave-gather
        collective and the bitmap form of ``scored`` is the local column
        slice (callers use :func:`sharded_greedy_search`, which sets all of
        this up).
      dedup / set_capacity: dedup-state backend — ``"auto"`` (default)
        resolves via :func:`resolve_dedup` with ``drive="fused"`` (this is
        one jitted while_loop, where the aliased bitmap carry wins on CPU);
        ``"bitmap"`` / ``"sorted"`` force a backend (``"sorted"`` = the
        O(quota)-state :class:`ScoredSet`, the memory-bound choice). The
        backends are bit-exact to each other; ``SearchResult.scored`` is
        always the (B, N) bitmap (the sorted backend materializes it once,
        after the loop).

    Returns a batch-leading SearchResult, pools sorted ascending by distance
    (under ``shard`` with the bitmap backend, ``scored`` is the local
    (B, n_local) slice).
    """
    adjacency = adjacency.astype(jnp.int32)
    n, _ = adjacency.shape
    assert n == n_points
    b, e0 = entry_ids.shape
    L = beam_width
    if isinstance(L, int) or getattr(L, "ndim", 0) == 0:
        L = int(L)
        P = max(pool_size or 0, L, e0)
        if max_steps is None:
            max_steps = 4 * L + 16
    else:
        if pool_size is None:
            raise ValueError(
                "a per-query (B,) beam_width needs an explicit pool_size")
        if max_steps is None:
            raise ValueError(
                "a per-query (B,) beam_width needs an explicit max_steps")
        # keep the scalar branch's P >= L invariant when the widths are
        # concrete (eager callers); under a trace the caller must guarantee
        # pool_size >= max(beam_width) — sharded_greedy_search does
        try:
            bw_cap = int(jnp.max(jnp.asarray(L)))
        except jax.errors.ConcretizationTypeError:
            bw_cap = 0
        P = max(pool_size, bw_cap, e0)
    dedup, set_capacity = resolve_dedup(
        dedup, set_capacity, quota, n_points, scored_init, drive="fused")
    be = kernel_backend.resolve_backend(
        backend, use_fused_merge=use_fused_merge, interpret=interpret,
        _caller="beam.batched_greedy_search")
    quota = jnp.broadcast_to(jnp.asarray(quota, jnp.int32), (b,))

    state, safe, keep = init_state(
        entry_ids,
        n_points=n_points,
        pool_size=P,
        quota=quota,
        scored_init=scored_init,
        calls_init=calls_init,
        shard=shard,
        dedup=dedup,
        set_capacity=set_capacity,
    )
    state = commit_scores(
        state, safe, keep, dist_fn_batch(query_ctx, safe), backend=be,
    )

    def cond(s: BatchedSearchState) -> Array:
        return active_mask(
            s, beam_width=L, quota=quota, max_steps=max_steps
        ).any()

    def body(s: BatchedSearchState) -> BatchedSearchState:
        s, safe, keep, _ = plan_step(
            s,
            adjacency,
            beam_width=L,
            quota=quota,
            max_steps=max_steps,
            expand_width=expand_width,
            shard=shard,
        )
        return commit_scores(
            s, safe, keep, dist_fn_batch(query_ctx, safe), backend=be,
        )

    final = lax.while_loop(cond, body, state)
    scored = final.scored
    if isinstance(scored, ScoredSet):
        # one scatter outside the hot loop keeps the result's scored field
        # backend-independent (bit-identical to the bitmap backend's)
        scored = scored_set_to_bitmap(scored, n_points)
    return SearchResult(
        final.pool_ids,
        final.pool_dists,
        scored,
        final.n_calls,
        final.n_steps,
    )


def fused_dist_fn(
    corpus: Array,
    metric: str = "sqeuclidean",
    *,
    backend: str | kernel_backend.Backend | None = None,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
    quantize: str | None = None,
) -> Callable[[Array, Array], Array]:
    """A ``dist_fn_batch`` that runs the backend-dispatched gather→score.

    ``query_ctx`` must then be the (B, dim) query embeddings. The default
    backend is the jnp gather-then-reduce oracle, which matches
    ``EmbeddingMetric`` up to fp association; the matmul backends
    (``"xla_matmul"`` / ``"pallas"`` / ``"auto"``) build the corpus-norm
    cache **here, once** — the returned closure threads the prebuilt
    :class:`repro.kernels.CorpusView` through every wave, so ``‖x‖²`` is
    never re-reduced inside the hot loop. ``quantize`` (or a Backend
    carrying the mode) builds the view with quantized residency — also
    here, once; a prebuilt (possibly quantized) view passes straight
    through and is scored as-is.
    """
    be = kernel_backend.resolve_backend(
        backend, use_pallas=use_pallas, interpret=interpret,
        quantize=quantize, _caller="beam.fused_dist_fn")
    if (be.matmul or be.quantize is not None
            or isinstance(corpus, kernel_backend.CorpusView)):
        src = kernel_backend.as_corpus_view(corpus, quantize=be.quantize)
    else:
        src = corpus

    def fn(q_embs: Array, ids: Array) -> Array:
        return ops.gather_score(src, q_embs, ids, metric=metric, backend=be)

    return fn


def sharded_greedy_search(
    corpus: Array,
    adjacency: Array,
    query_embs: Array,
    entry_ids: Array,
    *,
    shards: int,
    metric: str = "sqeuclidean",
    mesh=None,
    axis_name: str | None = None,
    beam_width: int | Array,
    pool_size: int | None = None,
    quota: int | Array = NO_QUOTA,
    expand_width: int = 1,
    max_steps: int | Array | None = None,
    backend: str | kernel_backend.Backend | None = None,
    use_pallas: bool | None = None,
    use_fused_merge: bool | None = None,
    interpret: bool | None = None,
    quantize: str | None = None,
    dedup: str = "auto",
    set_capacity: int | None = None,
) -> SearchResult:
    """Device-parallel batched greedy search over a sharded corpus.

    The corpus is split into ``shards`` contiguous row blocks, one per
    device of a 1-D mesh (built over the first ``shards`` local devices when
    ``mesh`` is None). Inside ``shard_map`` each device gathers and scores
    the wave lanes it owns with the fused local gather→score kernel; a psum
    over the shard axis reconstructs the replicated wave. Pools, call
    counters and step counters are replicated — every device runs the
    identical plan and merge, so the result (including the scored set) is
    **bit-exact** vs :func:`batched_greedy_search` with
    :func:`fused_dist_fn` on one device.

    Dedup state under the mesh (``dedup`` resolves like the unsharded
    engine's): the ``bitmap`` backend column-shards the (B, N) bitmap —
    lookups psum-OR the owning shard's answer, scatters land on the owner —
    while the ``sorted`` backend keeps the (B, quota) :class:`ScoredSet`
    replicated like the pools, so its per-device dedup state is independent
    of both N and the shard count and its membership ops are
    collective-free.

    ``backend`` selects the wave-scoring/merge route
    (``repro.kernels.resolve_backend``); the matmul backends build the
    corpus-norm cache once on the host and shard the norms **with** the
    corpus blocks (same contiguous placement, zero-padded rows carry norm
    0), so the cache adds nothing to the wave's psum traffic. ``quantize``
    (or a Backend carrying the mode, or a prebuilt quantized view as
    ``corpus``) holds the resident blocks as int8/fp8 codes with the
    per-row dequant parameters sharded alongside the norms — pad rows
    dequantize to exact zeros, and the replicated pools/counters make the
    quantized sharded run bit-exact vs the quantized unsharded run for
    the same view. The parity guarantee is per-backend: sharded ==
    unsharded under the *same* backend (the ``"ref"`` default additionally
    stays bit-exact vs the legacy engine).

    ``shards=1`` short-circuits to the single-device engine (today's path).
    """
    from jax.sharding import PartitionSpec as _P

    from repro.distributed import collectives
    from repro.distributed.sharding import (SEARCH_AXIS, search_mesh,
                                            shard_corpus, shard_corpus_view)

    n_points = kernel_backend.corpus_rows(corpus).shape[0]
    be = kernel_backend.resolve_backend(
        backend, use_pallas=use_pallas, use_fused_merge=use_fused_merge,
        interpret=interpret, quantize=quantize,
        _caller="beam.sharded_greedy_search")
    if shards == 1:
        return batched_greedy_search(
            fused_dist_fn(corpus, metric, backend=be),
            adjacency, query_embs, entry_ids, n_points=n_points,
            beam_width=beam_width, pool_size=pool_size, quota=quota,
            expand_width=expand_width, max_steps=max_steps,
            backend=be, dedup=dedup, set_capacity=set_capacity)
    # resolve the backend on the host (quota is concrete here) so the mesh
    # program is built against one concrete dedup structure
    dedup, set_capacity = resolve_dedup(
        dedup, set_capacity, quota, n_points, drive="fused")

    axis = axis_name or SEARCH_AXIS
    # the resident form is static on the host: a view is built (and
    # quantized) here exactly once, with the norms and dequant parameters
    # sharded like the row blocks — nothing metadata enters the wave psum
    quant = be.quantize
    if quant is None and isinstance(corpus, kernel_backend.CorpusView):
        quant = corpus.quantize
    need_view = be.matmul or quant is not None
    if need_view:
        (stacked, sq_stack, inv_stack, sc_stack, zp_stack,
         n_local) = shard_corpus_view(corpus, shards, quantize=be.quantize)
    else:
        stacked, n_local = shard_corpus(
            kernel_backend.corpus_rows(corpus), shards)
        sq_stack = jnp.zeros((shards, 0), jnp.float32)
        inv_stack = jnp.zeros((shards, 0), jnp.float32)
        sc_stack = jnp.zeros((shards, 0), jnp.float32)
        zp_stack = jnp.zeros((shards, 0), jnp.float32)
    has_zp = quant is not None and zp_stack.shape[-1] > 0
    mesh = mesh if mesh is not None else search_mesh(shards, axis)
    ctx = ShardCtx(axis_name=axis, n_local=n_local)
    b, e0 = entry_ids.shape
    quota_arr = jnp.broadcast_to(jnp.asarray(quota, jnp.int32), (b,))
    # static shape params from the per-query knobs (scalar knobs keep the
    # historical values exactly); the (B,) vectors ride in as operands so a
    # mixed-width batch does not retrace per composition
    bw_max = int(jnp.max(jnp.asarray(beam_width)))
    pool = max(pool_size or 0, bw_max, e0)
    if max_steps is None:
        max_steps = 4 * bw_max + 16
    bw_arr = _per_query(beam_width, b)
    ms_arr = _per_query(max_steps, b)

    def program(local_corpus, local_sq, local_inv, local_sc, local_zp,
                adj, q_embs, entries, q, bw, ms):
        local_corpus = local_corpus[0]  # (1, n_local, dim) block -> local rows
        if need_view:
            local_src = kernel_backend.CorpusView(
                rows=local_corpus, sq_norms=local_sq[0],
                inv_norms=local_inv[0],
                scales=local_sc[0] if quant is not None else None,
                zero_points=local_zp[0] if has_zp else None)
        else:
            local_src = local_corpus

        def dist_fn(qe, ids):
            return collectives.wave_gather_score(
                local_src, qe, ids, axis_name=axis, metric=metric,
                backend=be)

        return batched_greedy_search(
            dist_fn, adj, q_embs, entries, n_points=n_points,
            beam_width=bw, pool_size=pool, quota=q,
            expand_width=expand_width, max_steps=ms,
            backend=be, shard=ctx,
            dedup=dedup, set_capacity=set_capacity)

    rep2, rep1 = _P(None, None), _P(None)
    # bitmap: local column slices -> global (B, S*nl); sorted: the program
    # materializes the replicated (B, N) bitmap from the replicated set
    scored_spec = _P(None, axis) if dedup == "bitmap" else rep2
    res = jax.shard_map(
        program,
        mesh=mesh,
        check_vma=False,
        in_specs=(_P(axis, None, None), _P(axis, None), _P(axis, None),
                  _P(axis, None), _P(axis, None),
                  rep2, rep2, rep2, rep1, rep1, rep1),
        out_specs=SearchResult(
            pool_ids=rep2, pool_dists=rep2, scored=scored_spec,
            n_calls=rep1, n_steps=rep1),
    )(stacked, sq_stack, inv_stack, sc_stack, zp_stack,
      adjacency.astype(jnp.int32), query_embs,
      entry_ids.astype(jnp.int32), quota_arr, bw_arr, ms_arr)
    if dedup == "bitmap":
        # drop the zero-padding columns (global ids >= N never get scored)
        res = res._replace(scored=res.scored[:, :n_points])
    return res


class ShardedStepper:
    """Host-driven plan/commit stepping with the search state resident on a
    corpus mesh — the device side of the serving engine's stage 2.

    The serving engine cannot score inside a ``while_loop`` (its expensive
    metric is a lazily-evaluated model forward pass), so it drives
    :func:`plan_step` / :func:`commit_scores` from the host. This class is
    the sharded form of that drive loop: each method is a jitted
    ``shard_map`` program over the corpus mesh. The dedup state follows the
    backend chosen at :meth:`init`: the ``bitmap`` form lives as
    (B, n_local) column slices — the lookup OR-reduces the owning shard's
    answer, the scatter lands on the owner only
    (``repro.distributed.collectives``) — while the ``sorted``
    :class:`ScoredSet` form is replicated like the pools, shrinking the
    per-device dedup state from (B, n_local) to (B, quota) and making every
    membership op collective-free; both exactly like stage 1's
    :func:`sharded_greedy_search`. Pools, call and step counters stay
    replicated, every device plans the identical wave, and the host sees
    replicated ``safe`` / ``keep`` lanes to drain through the tower — so the
    sharded stage 2 is **bit-exact** vs the single-device drive loop under
    either backend.

    State produced by :meth:`init` must be threaded through :meth:`plan` /
    :meth:`commit` unmodified — its ``scored`` leaf carries the mesh
    sharding (or replication) between calls; everything stays on device
    until the final pools are read off. ``beam_width`` / ``max_steps`` /
    ``quota`` are (B,) operands, so mixed per-query budgets in one wave do
    not retrace; the sorted backend's capacity is a static shape, so
    callers should quantize it (the engine rounds up to a power of two) to
    keep retraces bounded.
    """

    def __init__(self, *, shards: int, n_points: int, mesh=None,
                 axis_name: str | None = None,
                 backend: str | kernel_backend.Backend | None = None):
        from repro.distributed.sharding import SEARCH_AXIS, search_mesh

        self.shards = shards
        self.n_points = n_points
        self.axis_name = axis_name or SEARCH_AXIS
        self.mesh = mesh if mesh is not None else search_mesh(
            shards, self.axis_name)
        self.n_local = -(-n_points // shards)
        self.ctx = ShardCtx(axis_name=self.axis_name, n_local=self.n_local)
        # merge route for commit (the stepper never scores — its caller's
        # tower does — so the backend only picks the pool-merge kernel)
        self.backend = kernel_backend.resolve_backend(
            backend, _caller="beam.ShardedStepper")
        self._programs: dict = {}

    # ------------------------------------------------------------- internals
    def _specs(self, dedup: str = "bitmap"):
        from jax.sharding import PartitionSpec as _P

        rep2, rep1 = _P(None, None), _P(None)
        scored_spec = (
            ScoredSet(ids=rep2, count=rep1)  # replicated, like the pools
            if dedup == "sorted" else _P(None, self.axis_name))
        state_spec = BatchedSearchState(
            pool_ids=rep2, pool_dists=rep2, expanded=rep2,
            scored=scored_spec, n_calls=rep1, n_steps=rep1)
        return rep2, rep1, state_spec

    @staticmethod
    def _dedup_of(state: BatchedSearchState) -> str:
        return "sorted" if isinstance(state.scored, ScoredSet) else "bitmap"

    def _program(self, key, build):
        if key not in self._programs:
            self._programs[key] = build()
        return self._programs[key]

    # -------------------------------------------------------------- step API
    def init(self, entry_ids: Array, quota: Array, *, pool_size: int,
             dedup: str = "bitmap", set_capacity: int | None = None,
             ) -> tuple[BatchedSearchState, Array, Array]:
        """Sharded :func:`init_state`: the entry wave, dedup state
        column-sharded (bitmap) or replicated (sorted). ``dedup`` must be
        concrete here — the engine resolves "auto" and quantizes
        ``set_capacity`` before calling."""
        rep2, rep1, state_spec = self._specs(dedup)

        def build():
            def f(entries, q):
                return init_state(
                    entries, n_points=self.n_points, pool_size=pool_size,
                    quota=q, shard=self.ctx, dedup=dedup,
                    set_capacity=set_capacity)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False, in_specs=(rep2, rep1),
                out_specs=(state_spec, rep2, rep2)))

        return self._program(("init", pool_size, dedup, set_capacity),
                             build)(
            jnp.asarray(entry_ids, jnp.int32), _per_query(
                quota, entry_ids.shape[0]))

    def plan(self, state: BatchedSearchState, adjacency: Array, quota: Array,
             beam_width: Array, max_steps: Array,
             *, expand_width: int | Array = 1,
             expand_cap: int | None = None,
             level: Array | None = None,
             wave_dedup: bool = True,
             ) -> tuple[BatchedSearchState, Array, Array, Array]:
        """Sharded :func:`plan_step` (owner-only scatter + psum lookup for
        the bitmap backend; collective-free replicated membership for the
        sorted backend). ``expand_width`` may be a (B,) vector — it rides
        in as an operand, the program is keyed on the static lane cap.
        ``level`` (a (B,) vector) selects slabs of a level-stacked
        ``(L, N, R)`` fanout table (replicated, like the flat one) — the
        cover-tree descent's program shape."""
        from jax.sharding import PartitionSpec as _P

        dedup = self._dedup_of(state)
        rep2, rep1, state_spec = self._specs(dedup)
        if expand_cap is None:
            expand_cap = _static_quota_bound(expand_width)
            if expand_cap is None:
                raise ValueError(
                    "a traced (B,) expand_width needs a static expand_cap")
        cap = max(int(expand_cap), 1)
        has_level = level is not None
        adj_spec = _P(*([None] * adjacency.ndim))

        def build():
            if has_level:
                def f(s, adj, q, bw, ms, ew, lev):
                    return plan_step(
                        s, adj, beam_width=bw, quota=q, max_steps=ms,
                        expand_width=ew, expand_cap=cap, shard=self.ctx,
                        level=lev, wave_dedup=wave_dedup)

                return jax.jit(jax.shard_map(
                    f, mesh=self.mesh, check_vma=False,
                    in_specs=(state_spec, adj_spec, rep1, rep1, rep1, rep1,
                              rep1),
                    out_specs=(state_spec, rep2, rep2, rep1)))

            def f(s, adj, q, bw, ms, ew):
                return plan_step(
                    s, adj, beam_width=bw, quota=q, max_steps=ms,
                    expand_width=ew, expand_cap=cap, shard=self.ctx,
                    wave_dedup=wave_dedup)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False,
                in_specs=(state_spec, adj_spec, rep1, rep1, rep1, rep1),
                out_specs=(state_spec, rep2, rep2, rep1)))

        b = state.pool_ids.shape[0]
        key = ("plan", cap, dedup, has_level, wave_dedup, adjacency.ndim)
        operands = (
            state, adjacency.astype(jnp.int32), _per_query(quota, b),
            _per_query(beam_width, b), _per_query(max_steps, b),
            _per_query(expand_width, b))
        if has_level:
            operands = (*operands, _per_query(level, b))
        return self._program(key, build)(*operands)

    def reopen(self, state: BatchedSearchState,
               rows: Array) -> BatchedSearchState:
        """Sharded :func:`reset_expanded` — re-open the masked rows'
        frontiers between cover-tree levels (pools and dedup untouched)."""
        dedup = self._dedup_of(state)
        _, rep1, state_spec = self._specs(dedup)

        def build():
            def f(s, r):
                return reset_expanded(s, r)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False,
                in_specs=(state_spec, rep1), out_specs=state_spec))

        b = state.pool_ids.shape[0]
        return self._program(("reopen", dedup), build)(
            state, jnp.broadcast_to(jnp.asarray(rows, bool), (b,)))

    def commit(self, state: BatchedSearchState, safe: Array, keep: Array,
               dists: Array) -> BatchedSearchState:
        """Sharded :func:`commit_scores` (replicated merge, dedup untouched)."""
        dedup = self._dedup_of(state)
        rep2, _, state_spec = self._specs(dedup)
        be = self.backend

        def build():
            def f(s, sf, kp, d):
                return commit_scores(s, sf, kp, d, backend=be)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False,
                in_specs=(state_spec, rep2, rep2, rep2),
                out_specs=state_spec))

        return self._program(("commit", dedup, be), build)(
            state, safe, keep, jnp.asarray(dists, jnp.float32))

    def admit(self, state: BatchedSearchState, reset: Array,
              entry_ids: Array, quota: Array,
              ) -> tuple[BatchedSearchState, Array, Array]:
        """Sharded :func:`reset_slots`: recycle the ``reset`` rows of a
        resident state for newly admitted queries (the slot pool's
        admission step). Non-reset rows pass through bit-exactly; the
        returned entry wave commits as a no-op on them."""
        dedup = self._dedup_of(state)
        rep2, rep1, state_spec = self._specs(dedup)

        def build():
            def f(s, rs, entries, q):
                return reset_slots(s, rs, entries, q, shard=self.ctx)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False,
                in_specs=(state_spec, rep1, rep2, rep1),
                out_specs=(state_spec, rep2, rep2)))

        b = state.pool_ids.shape[0]
        return self._program(("admit", dedup), build)(
            state, jnp.asarray(reset, bool),
            jnp.asarray(entry_ids, jnp.int32), _per_query(quota, b))

    def active(self, state: BatchedSearchState, quota: Array,
               beam_width: Array, max_steps: Array) -> Array:
        """Replicated per-row :func:`active_mask` — the slot pool reads it
        every step to detect finished slots (occupied & ~active)."""
        dedup = self._dedup_of(state)
        _, rep1, state_spec = self._specs(dedup)

        def build():
            def f(s, q, bw, ms):
                return active_mask(s, beam_width=bw, quota=q, max_steps=ms)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False,
                in_specs=(state_spec, rep1, rep1, rep1), out_specs=rep1))

        b = state.pool_ids.shape[0]
        return self._program(("active_mask", dedup), build)(
            state, _per_query(quota, b), _per_query(beam_width, b),
            _per_query(max_steps, b))

    def active_any(self, state: BatchedSearchState, quota: Array,
                   beam_width: Array, max_steps: Array) -> bool:
        """Replicated ``active_mask(...).any()`` — the host loop condition."""
        from jax.sharding import PartitionSpec as _P

        dedup = self._dedup_of(state)
        _, rep1, state_spec = self._specs(dedup)

        def build():
            def f(s, q, bw, ms):
                return active_mask(
                    s, beam_width=bw, quota=q, max_steps=ms).any()

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False,
                in_specs=(state_spec, rep1, rep1, rep1), out_specs=_P()))

        b = state.pool_ids.shape[0]
        return bool(self._program(("active", dedup), build)(
            state, _per_query(quota, b), _per_query(beam_width, b),
            _per_query(max_steps, b)))

    def scored_count(self, state: BatchedSearchState) -> Array:
        """(B,) distinct scored ids. Bitmap backend: psum of local popcounts
        — the partition invariant (no bit duplicated across shards, none
        lost). Sorted backend: the replicated set's unique count — the
        replication invariant (every device computes the same answer)."""
        from repro.distributed import collectives

        dedup = self._dedup_of(state)
        _, rep1, state_spec = self._specs(dedup)

        def build():
            def f(s):
                if isinstance(s.scored, ScoredSet):
                    return collectives.member_count(
                        s.scored.ids, axis_name=self.axis_name)
                return collectives.bitmap_count(
                    s.scored, axis_name=self.axis_name)

            return jax.jit(jax.shard_map(
                f, mesh=self.mesh, check_vma=False, in_specs=(state_spec,),
                out_specs=rep1))

        return self._program(("count", dedup), build)(state)


def greedy_search(
    dist_fn: Callable[[Array], Array],
    adjacency: Array,
    entry_ids: Array,
    *,
    n_points: int,
    beam_width: int,
    pool_size: int | None = None,
    quota: int | Array = NO_QUOTA,
    max_steps: int | None = None,
    scored_init: Array | None = None,
    calls_init: Array | int = 0,
) -> SearchResult:
    """Single-query wrapper over the batched engine (B = 1).

    ``dist_fn`` maps (k,) int32 vertex ids -> (k,) f32 distances to the query
    (ids < 0 -> +inf). Semantics are unchanged from the historical per-query
    engine: expand-one-vertex steps, exact quota, scored-bitmap dedup.
    """

    def dist_fn_batch(_ctx, ids):
        # vmapped even at B=1 so the lowering (and hence fp association) is
        # identical to a real batch — parity is bit-exact, not just close.
        return jax.vmap(dist_fn)(ids)

    res = batched_greedy_search(
        dist_fn_batch,
        adjacency,
        None,
        entry_ids[None, :],
        n_points=n_points,
        beam_width=beam_width,
        pool_size=pool_size,
        quota=quota,
        max_steps=max_steps,
        scored_init=None if scored_init is None else scored_init[None, :],
        calls_init=calls_init,
    )
    return SearchResult(*(a[0] for a in res))


def greedy_search_batch(
    dist_fn_batch: Callable[[Array, Array], Array],
    adjacency: Array,
    query_ctx: Array,
    entry_ids: Array,
    **kw,
) -> SearchResult:
    """Batched search with a *per-query* distance function (legacy contract).

    ``dist_fn_batch(q_ctx, ids)`` scores (k,) ids against one query context;
    it is vmapped over the batch and fed to the batched engine.
    ``query_ctx``: (B, ...) per-query context; ``entry_ids``: (B, E) or (E,).
    """
    if entry_ids.ndim == 1:
        entry_ids = jnp.broadcast_to(
            entry_ids, (query_ctx.shape[0], entry_ids.shape[0])
        )
    return batched_greedy_search(
        jax.vmap(dist_fn_batch), adjacency, query_ctx, entry_ids, **kw
    )
