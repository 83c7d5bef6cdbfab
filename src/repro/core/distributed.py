"""Distributed bi-metric search: scatter-gather over corpus shards.

Production ANN layout (what DiskANN/SPANN-scale deployments do):

* the corpus is split into S shards along the ``model`` mesh axis; each shard
  holds its own Vamana sub-index built **only with the proxy metric d**
  (shard-local builds are embarrassingly parallel — a net of a shard is a net
  of the union, so Theorem 1.1 applies per shard);
* queries are data-parallel along the ``data`` (and ``pod``) axes and
  replicated across ``model``;
* every device runs the two-stage bi-metric search on its local sub-index
  with a per-shard quota slice Q/S, then the per-shard top-k (tiny: k ids +
  dists) are all-gathered across ``model`` and merge-sorted into a global
  top-k by D. Total expensive calls = psum of the exact per-shard counters.

This file contains the shard_map program; mesh construction lives in
``repro.launch.mesh``.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import distances
from repro.core.bimetric import bimetric_search_single
from repro.core.vamana import VamanaConfig, VamanaIndex
from repro.distributed import collectives

Array = jax.Array


class ShardedIndex(NamedTuple):
    """Stacked per-shard sub-indices. Leading axis = shard (on mesh axis 'model')."""

    adjacency: Array  # (S, n_local, R)
    medoid: Array  # (S,)
    emb_cheap: Array  # (S, n_local, dim_d)
    emb_expensive: Array  # (S, n_local, dim_D)  (precomputed-D evaluation mode)
    config: VamanaConfig


def build_sharded(
    emb_cheap: Array,
    emb_expensive: Array,
    n_shards: int,
    cfg: VamanaConfig,
) -> ShardedIndex:
    """Split the corpus round-robin-contiguously and build per-shard graphs with d."""
    from repro.core import vamana

    n = emb_cheap.shape[0]
    assert n % n_shards == 0, "pad the corpus to a multiple of the shard count"
    nl = n // n_shards
    adj, med = [], []
    for s in range(n_shards):
        idx = vamana.build(emb_cheap[s * nl : (s + 1) * nl], cfg)
        adj.append(idx.adjacency)
        med.append(idx.medoid)
    return ShardedIndex(
        adjacency=jnp.stack(adj),
        medoid=jnp.stack(med),
        emb_cheap=emb_cheap.reshape(n_shards, nl, -1),
        emb_expensive=emb_expensive.reshape(n_shards, nl, -1),
        config=cfg,
    )


def _local_search(
    adjacency, medoid, emb_d, emb_D, q_d, q_D, *, quota, k, n_seeds, cfg
):
    """Bi-metric search on one shard for a block of queries."""
    n_local = emb_d.shape[0]
    em_d = distances.EmbeddingMetric(emb_d, cfg.metric)
    em_D = distances.EmbeddingMetric(emb_D, cfg.metric)
    index = VamanaIndex(adjacency=adjacency, medoid=medoid, config=cfg)

    def one(qd, qD):
        ids, dd, _, n_calls = bimetric_search_single(
            lambda i: em_d.dists(qd, i),
            lambda i: em_D.dists(qD, i),
            index,
            n_points=n_local,
            quota=quota,
            k=k,
            n_seeds=n_seeds,
        )
        return ids, dd, n_calls

    return jax.vmap(one)(q_d, q_D)


def sharded_bimetric_search(
    mesh: Mesh,
    index: ShardedIndex,
    q_cheap: Array,
    q_expensive: Array,
    *,
    quota: int,
    k: int = 10,
    data_axes: tuple[str, ...] = ("data",),
    model_axis: str = "model",
):
    """Scatter-gather bi-metric search across the mesh.

    Returns (global ids (B, k), D dists (B, k), total D calls (B,)).
    """
    s = index.adjacency.shape[0]
    n_local = index.adjacency.shape[1]
    per_shard_quota = max(k, quota // s)
    n_seeds = max(1, per_shard_quota // 2)
    cfg = index.config

    def program(adj, med, ed, eD, qd, qD):
        # shard_map slices the leading shard dim to size 1 on this device
        adj, med = adj[0], med[0]
        ed, eD = ed[0], eD[0]
        ids, dd, n_calls = _local_search(
            adj, med, ed, eD, qd, qD,
            quota=per_shard_quota, k=k, n_seeds=n_seeds, cfg=cfg,
        )
        shard = jax.lax.axis_index(model_axis)
        gids = jnp.where(ids >= 0, ids + shard * n_local, -1)
        # per-shard top-k cut before the all-gather: merge traffic is
        # (S, B_local, k), never the shard-local pools
        top_ids, top_dd = collectives.gather_topk_merge(
            gids, jnp.where(ids >= 0, dd, jnp.inf), k, axis_name=model_axis)
        calls = jax.lax.psum(n_calls, model_axis)
        return top_ids, top_dd, calls

    qspec = P(data_axes if len(data_axes) > 1 else data_axes[0], None)
    out = jax.shard_map(
        program,
        mesh=mesh,
        check_vma=False,
        in_specs=(P(model_axis), P(model_axis), P(model_axis), P(model_axis), qspec, qspec),
        out_specs=(qspec, qspec, P(data_axes if len(data_axes) > 1 else data_axes[0])),
    )(index.adjacency, index.medoid, index.emb_cheap, index.emb_expensive,
      q_cheap, q_expensive)
    return out
