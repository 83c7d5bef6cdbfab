"""Collective primitives: overlapped matmuls and the sharded-search wave ops.

Two families, both written against a named ``shard_map`` axis and verified on
an 8-device host mesh in tests:

**Ring collective-matmuls** — ``ppermute`` algorithms that interleave one
chunk of matmul with one chunk of neighbor exchange per step (the "collective
matmul" transformation, Wang et al. ASPLOS'23), provided explicitly for the
TP layers:

* ``allgather_matmul``:  computes  all_gather(x, axis) @ w  without ever
  materializing the gathered x: each ring step multiplies the resident chunk
  while the next chunk is in flight.
* ``matmul_reducescatter``: computes reduce_scatter(x @ w) chunk-by-chunk,
  sending partial sums around the ring.

**Sharded-search wave collectives** — the device-parallel form of the batched
beam engine's plan/commit step (``repro.core.beam``). Each device owns a
contiguous corpus block of ``n_local`` rows (global rows
``[idx * n_local, (idx + 1) * n_local)``) and the matching column slice of
every query's scored bitmap; pools stay replicated:

* ``wave_gather_score``: each shard scores the wave lanes it owns with the
  fused local gather→score kernel (foreign/padding lanes emit the psum
  identity 0.0) and a ``psum`` over the shard axis reconstructs the
  replicated (B, K) wave *bit-exactly* — each global id has exactly one
  owner and x + 0.0 == x.
* ``bitmap_lookup`` / ``bitmap_scatter``: membership tests OR-reduce the
  owning shard's answer across the axis; scatters land only on the owning
  shard's local columns. Both stage 1's in-``while_loop`` engine and the
  serving engine's host-driven stage 2 (``repro.core.beam.ShardedStepper``)
  run their bitmap traffic through these two ops.
* ``bitmap_count``: per-query psum popcount of the partitioned bitmap — the
  partition invariant (each bit owned by exactly one shard) makes the psum
  of local counts the exact global count.
* ``member_lookup`` / ``member_insert`` / ``member_count``: the same three
  operations against the **quota-proportional sorted dedup set**
  (``repro.core.beam.ScoredSet``). Unlike the column-sharded bitmap, the
  (B, quota) set is *replicated* like the pools — every device holds the
  identical ascending id rows — so all three are collective-free local ops:
  the per-device dedup state shrinks from (B, N/shards) to (B, quota) and
  the bitmap-lookup psum disappears from the wave entirely. The axis
  argument is accepted (and ignored) so call sites stay backend-agnostic.
* ``gather_topk_merge``: the scatter-gather merge — per-shard top-k cut
  (``ops.local_topk``) before an ``all_gather``, so merge traffic is O(k)
  per query instead of O(n_local).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import backend as kernel_backend
from repro.kernels import ops

Array = jax.Array


# --------------------------------------------------------------------------
# sharded-search wave collectives
# --------------------------------------------------------------------------
def shard_offset(axis_name: str, n_local: int) -> Array:
    """First global corpus row owned by this device (contiguous placement)."""
    return lax.axis_index(axis_name) * n_local


def wave_gather_score(corpus_local, queries: Array, ids: Array, *,
                      axis_name: str, metric: str = "sqeuclidean",
                      backend=None, use_pallas: bool | None = None,
                      interpret: bool | None = None) -> Array:
    """Device-parallel backend-dispatched gather→score of one wave.

    ``corpus_local`` is this device's corpus block — a raw (n_local, dim)
    array or its :class:`repro.kernels.CorpusView` (the matmul backends'
    norm cache — and a quantized view's per-row scale/zero-point
    metadata — shards with the rows, so it is a purely local operand);
    ``ids`` (B, K) is the replicated wave. Returns the replicated (B, K)
    distances, bit-exact vs the unsharded ``ops.gather_score`` under the
    same backend and residency (ids < 0 -> +inf). ``use_pallas`` /
    ``interpret`` are the deprecated shims for ``backend`` — resolved here
    at the API boundary so only a concrete Backend flows inward.
    """
    be = kernel_backend.resolve_backend(
        backend, use_pallas=use_pallas, interpret=interpret,
        _caller="collectives.wave_gather_score")
    rows = kernel_backend.corpus_rows(corpus_local)
    part = ops.gather_score_local(
        corpus_local, queries, ids,
        shard_offset(axis_name, rows.shape[0]),
        metric=metric, backend=be)
    d = lax.psum(part, axis_name)
    return jnp.where(ids >= 0, d, jnp.inf)


def bitmap_lookup(scored_local: Array, ids: Array, *,
                  axis_name: str) -> Array:
    """Replicated membership test against the shard-column bitmap.

    ``scored_local`` (B, n_local) holds this device's column slice of the
    (B, N) scored bitmap; ``ids`` (B, K) are replicated global ids. Each
    shard answers for the lanes it owns and an OR (psum > 0) replicates the
    result. Lanes with id < 0 return False.
    """
    n_local = scored_local.shape[1]
    loc = ids - shard_offset(axis_name, n_local)
    owned = (ids >= 0) & (loc >= 0) & (loc < n_local)
    hit = jnp.take_along_axis(
        scored_local, jnp.clip(loc, 0, n_local - 1), axis=1) & owned
    return lax.psum(hit.astype(jnp.int32), axis_name) > 0


def bitmap_scatter(scored_local: Array, ids: Array, mark: Array, *,
                   axis_name: str) -> Array:
    """Set bitmap bits for the marked lanes on their owning shard (only).

    The scatter is local — no collective: each device updates the columns it
    owns and ignores foreign lanes, which keeps the (B, N) bitmap exactly
    partitioned across the axis (no bit is ever duplicated or dropped).
    """
    n_local = scored_local.shape[1]
    loc = ids - shard_offset(axis_name, n_local)
    owned = mark & (loc >= 0) & (loc < n_local)
    rows = jnp.arange(ids.shape[0])[:, None]
    # scatter-OR (max): foreign/padding lanes all alias column 0, so a
    # plain set() would race — mirrors repro.core.beam.init_state.
    return scored_local.at[rows, jnp.clip(loc, 0, n_local - 1)].max(owned)


def bitmap_count(scored_local: Array, *, axis_name: str) -> Array:
    """(B,) replicated global popcount of the shard-partitioned bitmap.

    ``scored_local`` (B, n_local) is this device's column slice. Because the
    scatter discipline keeps the global (B, N) bitmap exactly partitioned
    (every bit has one owner — see :func:`bitmap_scatter`), the psum of the
    local row counts *is* the global count; tests use this as the partition
    invariant for the sharded stage-2 drive loop.
    """
    return lax.psum(
        scored_local.sum(axis=1, dtype=jnp.int32), axis_name)


def member_lookup(set_ids: Array, ids: Array, *, axis_name: str) -> Array:
    """Membership test against the replicated sorted dedup set.

    ``set_ids`` (B, C) are the ascending id rows of a
    ``repro.core.beam.ScoredSet`` — replicated across the axis like the
    pools, so the lookup is one local ``searchsorted`` per row with no
    collective at all (compare :func:`bitmap_lookup`'s psum-OR).
    """
    del axis_name  # replicated state: no collective needed
    return ops.sorted_set_lookup(set_ids, ids)


def member_insert(set_ids: Array, ids: Array, mark: Array, *,
                  axis_name: str) -> Array:
    """Insert the marked lanes' ids into the replicated sorted set.

    Every device performs the identical merge on identical replicated
    inputs, which *is* the replication invariant — the sorted-set analogue
    of :func:`bitmap_scatter`'s owner-only discipline.
    """
    del axis_name
    return ops.sorted_set_merge(
        set_ids, jnp.where(mark, ids, ops.SET_PAD))


def member_count(set_ids: Array, *, axis_name: str) -> Array:
    """(B,) distinct scored ids in the replicated set — the exact number
    :func:`bitmap_count` psums out of the partitioned bitmap, computed
    locally (duplicate slots from the E=1 duplicate-lane quirk collapse).
    """
    del axis_name
    return ops.sorted_set_unique_count(set_ids)


def gather_topk_merge(ids_local: Array, dists_local: Array, k: int, *,
                      axis_name: str) -> tuple[Array, Array]:
    """Per-shard top-k cut, then all-gather + merge into a global top-k.

    ``ids_local`` / ``dists_local`` (B, P) are each shard's candidates with
    *global* ids (+inf-padded). Each shard keeps only its k best before the
    collective, so the gather moves (S, B, k) instead of (S, B, P). Ties
    across shards resolve to the lower shard index (the all-gather is
    axis-ordered and the final cut is a stable top-k). Pools narrower than
    ``k`` are padded to width k with (-1, +inf) sentinels by the cut itself.
    """
    lids, ld = ops.local_topk(ids_local, dists_local, k)
    all_ids = lax.all_gather(lids, axis_name)  # (S, B, k)
    all_d = lax.all_gather(ld, axis_name)
    all_ids = jnp.moveaxis(all_ids, 0, 1).reshape(ids_local.shape[0], -1)
    all_d = jnp.moveaxis(all_d, 0, 1).reshape(ids_local.shape[0], -1)
    return ops.local_topk(all_ids, all_d, k)


def allgather_matmul(x: Array, w: Array, axis_name: str) -> Array:
    """x: (m_local, k) shard of a row-sharded M×K; w: (k, n) local weight.

    Returns (m_local * n_dev, n) — the full all_gather(x) @ w, computed by
    rotating shards around the ring and filling the output block that each
    incoming shard corresponds to. One send/recv overlaps one block matmul.
    """
    n_dev = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m_local = x.shape[0]
    out = jnp.zeros((m_local * n_dev, w.shape[1]), w.dtype)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(i, carry):
        out, chunk = carry
        src = (idx - i) % n_dev  # whose shard we currently hold
        block = chunk @ w
        out = lax.dynamic_update_slice(out, block.astype(out.dtype),
                                       (src * m_local, 0))
        chunk = lax.ppermute(chunk, axis_name, perm)
        return out, chunk

    out, _ = lax.fori_loop(0, n_dev, body, (out, x))
    return out


def matmul_reducescatter(x: Array, w: Array, axis_name: str) -> Array:
    """x: (m, k_local) shard of a col-sharded M×K; w: (k_local, n) local shard
    of a row-sharded K×N. Returns the (m/n_dev, n) reduce-scattered product of
    the full x @ w, accumulating partial sums as they travel the ring.
    """
    n_dev = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    m = x.shape[0]
    assert m % n_dev == 0, "row count must divide the axis size"
    m_local = m // n_dev
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def block(i):
        # chunk held by this device at ring step i: the accumulator for
        # output chunk c visits device (c + 1 + i) mod n at step i, so the
        # resident chunk here is c = (idx - i - 1) mod n. After n-1 hops the
        # accumulator for chunk idx lands home.
        row = ((idx - i - 1) % n_dev) * m_local
        return lax.dynamic_slice(x, (row, 0), (m_local, x.shape[1])) @ w

    def body(i, acc):
        acc = acc + block(i)
        return lax.ppermute(acc, axis_name, perm)

    # n_dev-1 hops with accumulation, final block added without a hop
    acc = jnp.zeros((m_local, w.shape[1]), jnp.result_type(x.dtype, w.dtype))
    acc = lax.fori_loop(0, n_dev - 1, body, acc)
    acc = acc + block(n_dev - 1)
    return acc
