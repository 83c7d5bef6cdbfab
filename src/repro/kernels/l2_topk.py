"""Fused gather → score → beam-merge kernels (the bi-metric beam step).

This is the query-time hot loop of the paper's method on TPU: each batched
greedy-search step scores the expanded frontier's fanout against the queries
and merges the results into the per-query pools. Every block below is
(8, 128)-tiled or spans its whole array, which is what the TPU compiler
accepts. Two kernels:

* ``gather_score`` — a grid over tiles of :data:`QUERY_TILE` queries. XLA
  gathers the candidate rows beside the call into a ``(B, K, dim)`` block
  in the corpus dtype (int8/fp8 codes stay codes): the chip's compiler
  refuses a one-row DMA out of an (8, 128)-tiled corpus, and an aligned
  8-row (32 for int8) fetch per id would move 8x (32x) the row bytes. Each
  tile reads its ``(QUERY_TILE, K, dim)`` block with K on sublanes, runs
  the metric reduction (l2 / sqeuclidean / ip / cosine, matching
  ``repro.core.distances``) per query on its ``(K, dim)`` rows, and stores
  one lane-dense ``(K, 128)`` block whose lane b holds query b's scores
  (transposed back to (B, K) by XLA after the call). With the ``norms``
  operand (the row metadata of ``repro.kernels.backend.CorpusView``,
  packed by :func:`pack_row_meta` and gathered beside the rows) the score
  is computed in **matmul form** — ``‖x‖² − 2·⟨x, q⟩ + ‖q‖²`` with the
  row-norm term read from the cache instead of re-reduced, which drops the
  subtract-square pass and leaves one multiply-reduce per lane; the
  4-column form dequantizes the codes in VMEM right before that dot.
  Without ``norms`` the gather-then-reduce body runs.
  ``gather_score_local`` is the shard-local form: it remaps global ids to
  the local row block in XLA and runs the same tile;
* ``beam_merge_topk`` — bitonic merge network over the (beam ‖ fanout) rows,
  8 query rows per grid step over an ``(8, n_pad)`` block. Each
  compare-exchange finds its partner lane (index XOR j) with two
  ``pltpu.roll`` lane rotations and a select, so it lowers to vector
  rotates and selects (no sort primitive, no reshape). Optionally carries
  an int32 payload lane (the pool's ``expanded`` flags) through the same
  network so the batched engine can merge its full (ids, dists, expanded)
  pool state in one call. The network is padded to a power of two **and
  to the 128-wide TPU lane** (``MERGE_LANE``), and the output block is
  lane-aligned too (sliced back to L outside the kernel) — so
  non-power-of-two and non-lane-multiple pools run the fused merge.

Pure-jnp oracles for both live in ``repro.kernels.ref`` (the CPU/interpret
fallback path used by the core engine off-TPU); backend selection for all of
this lives in ``repro.kernels.backend`` / ``repro.kernels.ops``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import NORM_EPS, CorpusView

Array = jax.Array

VALID_METRICS = ("l2", "sqeuclidean", "ip", "cosine")

MERGE_LANE = 128  # TPU vector lane width — merge rows are padded to it
QUERY_TILE = 8  # queries (scoring) / pool rows (merge) per grid step


def pack_norms(view: CorpusView) -> Array:
    """(N, 2) f32 kernel operand: column 0 = ‖x‖², column 1 = 1/‖x‖."""
    return jnp.stack([view.sq_norms, view.inv_norms], axis=1)


def pack_row_meta(view: CorpusView) -> Array:
    """(N, 2) or (N, 4) f32 row-metadata operand for the scoring tile.

    The generalization of :func:`pack_norms`: columns ``[‖x‖², 1/‖x‖]``
    for a raw view, ``[‖x‖², 1/‖x‖, scale, zero_point]`` for a quantized
    one (the zero-point column is 0.0 for the symmetric fp8 modes, so one
    in-tile dequant ``(code - zp) * scale`` serves int8 and fp8 alike).
    The column count selects the kernel body in :func:`gather_score`.
    """
    cols = [view.sq_norms, view.inv_norms]
    if view.scales is not None:
        cols.append(view.scales.astype(jnp.float32))
        zp = view.zero_points
        cols.append(jnp.zeros_like(view.scales) if zp is None
                    else zp.astype(jnp.float32))
    return jnp.stack(cols, axis=1)


# --------------------------------------------------------------------------
# fused gather + score
# --------------------------------------------------------------------------
def _score_block(q, rows, meta, *, metric: str):
    """(1, dim) query vs (K, dim) rows -> (K, 1) scores.

    ``meta`` is None (gather-then-reduce, matches ``ref.gather_score_ref``)
    or the (K, 2|4) row metadata (matmul form; 4 columns dequantize first).
    """
    def rsum(x):
        return jnp.sum(x, axis=1, keepdims=True)

    if meta is None:
        if metric in ("l2", "sqeuclidean"):
            diff = q - rows
            d = rsum(diff * diff)
            return jnp.sqrt(d) if metric == "l2" else d
        if metric == "ip":
            return -rsum(q * rows)
        qn = jax.lax.rsqrt(rsum(q * q) + NORM_EPS)
        rn = jax.lax.rsqrt(rsum(rows * rows) + NORM_EPS)
        return 1.0 - rsum(q * rows) * qn * rn
    if meta.shape[1] == 4:  # in-VMEM dequant: ref.dequant_rows_ref exactly
        rows = (rows - meta[:, 3:4]) * meta[:, 2:3]
    dot = rsum(q * rows)
    if metric in ("l2", "sqeuclidean"):
        # the expansion can dip epsilon-negative where the oracle is ~0
        d = jnp.maximum(meta[:, 0:1] - 2.0 * dot + rsum(q * q), 0.0)
        return jnp.sqrt(d) if metric == "l2" else d
    if metric == "ip":
        return -dot
    return 1.0 - dot * jax.lax.rsqrt(rsum(q * q) + NORM_EPS) * meta[:, 1:2]


def _gather_score_kernel(q_ref, rows_ref, *refs, metric: str,
                         with_meta: bool):
    meta_ref, o_ref = refs if with_meta else (None, refs[0])
    k_pad = rows_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (k_pad, MERGE_LANE), 1)
    out = jnp.zeros((k_pad, MERGE_LANE), jnp.float32)
    for b in range(QUERY_TILE):
        d = _score_block(q_ref[b:b + 1, :], rows_ref[b].astype(jnp.float32),
                         meta_ref[b] if with_meta else None, metric=metric)
        out = jnp.where(lane == b, d, out)
    o_ref[0] = out


def gather_score(corpus: Array, queries: Array, ids: Array, *,
                 metric: str = "sqeuclidean", norms: Array | None = None,
                 interpret: bool = False) -> Array:
    """corpus (N, dim); queries (B, dim); ids (B, K) -> (B, K) dissimilarities.

    Ids < 0 are padding and map to +inf. The metric names and conventions
    match ``repro.core.distances`` ("ip" is negated, "cosine" is one-minus).
    With ``norms`` (the packed (N, 2) or (N, 4) row-metadata operand, see
    :func:`pack_row_meta`) the matmul-form tile runs — the row-norm reduce
    is replaced by a cached load; the 4-column form additionally
    dequantizes int8/fp8 codes in VMEM before the dot.
    """
    if metric not in VALID_METRICS:
        raise ValueError(f"metric must be one of {VALID_METRICS}, got {metric!r}")
    b, dim = queries.shape
    k = ids.shape[1]
    # K rides the sublanes: pad it to the corpus dtype's packed tile height
    # (8 rows for 32-bit, 16 for 16-bit, 32 for 8-bit codes)
    sub = 32 // jnp.dtype(corpus.dtype).itemsize
    k_pad = -(-k // sub) * sub
    b_pad = -(-b // QUERY_TILE) * QUERY_TILE
    n_tiles = b_pad // QUERY_TILE
    safe = jnp.pad(jnp.maximum(ids, 0).astype(jnp.int32),
                   ((0, b_pad - b), (0, k_pad - k)))
    operands = [jnp.pad(queries.astype(jnp.float32), ((0, b_pad - b), (0, 0))),
                corpus[safe]]
    in_specs = [pl.BlockSpec((QUERY_TILE, dim), lambda t: (t, 0)),
                pl.BlockSpec((QUERY_TILE, k_pad, dim), lambda t: (t, 0, 0))]
    if norms is not None:
        ncols = norms.shape[1]
        operands.append(norms.astype(jnp.float32)[safe])
        in_specs.append(pl.BlockSpec((QUERY_TILE, k_pad, ncols),
                                     lambda t: (t, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_gather_score_kernel, metric=metric,
                          with_meta=norms is not None),
        grid=(n_tiles,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, k_pad, MERGE_LANE), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, k_pad, MERGE_LANE),
                                       jnp.float32),
        interpret=interpret,
        name="l2_gather_score",
    )(*operands)
    d = out[:, :k, :QUERY_TILE].transpose(0, 2, 1).reshape(b_pad, k)[:b]
    return jnp.where(ids >= 0, d, jnp.inf)


def gather_score_local(corpus_local: Array, queries: Array, ids: Array,
                       offset: Array, *, metric: str = "sqeuclidean",
                       norms: Array | None = None,
                       interpret: bool = False) -> Array:
    """Shard-local fused gather→score over *global* ids (see ref oracle).

    ``corpus_local`` (n_local, dim) is this shard's contiguous row block
    starting at global row ``offset`` (a traced scalar — inside ``shard_map``
    it is ``axis_index * n_local``). Owned lanes are remapped to local ids
    and scored by the :func:`gather_score` tile, so they carry exactly its
    per-lane value; foreign and padding lanes are masked to the psum
    identity 0.0. ``norms`` is the *local* block's packed row metadata
    ((n_local, 2) raw / (n_local, 4) quantized — it shards with the rows).
    """
    n_local = corpus_local.shape[0]
    ids = ids.astype(jnp.int32)
    loc = ids - jnp.asarray(offset, jnp.int32)
    owned = (ids >= 0) & (loc >= 0) & (loc < n_local)
    d = gather_score(corpus_local, queries, jnp.where(owned, loc, -1),
                     metric=metric, norms=norms, interpret=interpret)
    # psum identity on foreign/padding lanes — see ref.gather_score_local_ref
    return jnp.where(owned, d, 0.0)


# --------------------------------------------------------------------------
# bitonic beam merge
# --------------------------------------------------------------------------
def _merge_kernel(d_ref, i_ref, f_ref, od_ref, oi_ref, of_ref):
    d, idx, flg = d_ref[...], i_ref[...], f_ref[...]
    n = d.shape[1]
    pos = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    # full bitonic sort (ascending) of the 2^m-length rows
    m = n.bit_length() - 1
    for stage in range(1, m + 1):
        for sub in range(stage - 1, -1, -1):
            j = 1 << sub
            # partner lane = pos XOR j, i.e. pos + j or pos - j: rotate both
            # ways and keep the rotation whose source lane is the partner
            # (rolling the lane iota makes this independent of the rotate
            # direction convention)
            fwd = pltpu.roll(pos, j, 1) == (pos ^ j)

            def partner(x, j=j, fwd=fwd):
                return jnp.where(fwd, pltpu.roll(x, j, 1),
                                 pltpu.roll(x, n - j, 1))

            d_p, i_p, f_p = partner(d), partner(idx), partner(flg)
            # keep the min where the lane's half (bit ``sub``) agrees with
            # its segment's direction (bit ``stage``: set = descending)
            want_min = (((pos >> stage) ^ (pos >> sub)) & 1) == 0
            take_self = (want_min & (d <= d_p)) | (~want_min & (d >= d_p))
            d = jnp.where(take_self, d, d_p)
            idx = jnp.where(take_self, idx, i_p)
            flg = jnp.where(take_self, flg, f_p)
    w = od_ref.shape[1]
    od_ref[...] = d[:, :w]
    oi_ref[...] = idx[:, :w]
    of_ref[...] = flg[:, :w]


def beam_merge_topk(beam_ids: Array, beam_dists: Array, cand_ids: Array,
                    cand_dists: Array, *, beam_flags: Array | None = None,
                    cand_flags: Array | None = None, interpret: bool = False):
    """Merge (B, L) beam and (B, K) candidates -> best-(B, L). Bitonic in VMEM.

    The grid runs over tiles of :data:`QUERY_TILE` pool rows. When
    ``beam_flags`` is given, an int32 payload lane rides through the same
    compare-exchange network (the batched engine's ``expanded`` markers) and
    a third output is returned. Ties in distance (inf padding included) are
    broken by the network, not by input position — callers needing the
    stable-merge contract use ``repro.kernels.ref.merge_pool_batch_ref``.

    The network length is padded to a power of two **and** to
    :data:`MERGE_LANE` (the TPU vector lane width), and the output block is
    lane-aligned and sliced back to L after the call — arbitrary (L, K)
    shapes run the fused network. The output distances keep the inputs'
    promoted dtype (the compare-exchange runs on an exact f32 embedding for
    bf16/f16), so half-precision pools round-trip without upcasting.
    """
    b, L = beam_ids.shape
    k = cand_ids.shape[1]
    with_flags = beam_flags is not None
    if beam_flags is None:
        beam_flags = jnp.zeros((b, L), jnp.int32)
    if cand_flags is None:
        cand_flags = jnp.zeros((b, k), jnp.int32)
    d_dtype = jnp.result_type(beam_dists.dtype, cand_dists.dtype)
    n = L + k
    # power-of-two for the bitonic network, lane width for the TPU tiling
    n_pad = max(1 << (n - 1).bit_length(), MERGE_LANE)
    b_pad = -(-b // QUERY_TILE) * QUERY_TILE
    pad = ((0, b_pad - b), (0, n_pad - n))
    d = jnp.pad(jnp.concatenate([beam_dists.astype(jnp.float32),
                                 cand_dists.astype(jnp.float32)], axis=1),
                pad, constant_values=jnp.inf)
    idx = jnp.pad(jnp.concatenate([beam_ids.astype(jnp.int32),
                                   cand_ids.astype(jnp.int32)], axis=1),
                  pad, constant_values=-1)
    flg = jnp.pad(jnp.concatenate([beam_flags.astype(jnp.int32),
                                   cand_flags.astype(jnp.int32)], axis=1),
                  pad)
    # lane-aligned output block, sliced back to L below
    w = min(n_pad, -(-L // MERGE_LANE) * MERGE_LANE)
    in_spec = pl.BlockSpec((QUERY_TILE, n_pad), lambda t: (t, 0))
    out_spec = pl.BlockSpec((QUERY_TILE, w), lambda t: (t, 0))
    od, oi, of = pl.pallas_call(
        _merge_kernel,
        grid=(b_pad // QUERY_TILE,),
        in_specs=[in_spec] * 3,
        out_specs=[out_spec] * 3,
        out_shape=[jax.ShapeDtypeStruct((b_pad, w), jnp.float32),
                   jax.ShapeDtypeStruct((b_pad, w), jnp.int32),
                   jax.ShapeDtypeStruct((b_pad, w), jnp.int32)],
        interpret=interpret,
        name="l2_beam_merge_topk",
    )(d, idx, flg)
    oi = oi[:b, :L].astype(beam_ids.dtype)
    od = od[:b, :L].astype(d_dtype)
    if with_flags:
        return oi, od, of[:b, :L]
    return oi, od
