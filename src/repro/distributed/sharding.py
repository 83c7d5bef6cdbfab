"""Sharding rules: param/batch pytrees -> PartitionSpecs per arch family.

Axis roles (names must exist in the mesh):
* ``tp``   — tensor/expert parallel axis ("model");
* ``fsdp`` — parameter-sharding data axes ("data", and "pod" when present):
  every ≥2-D weight is sharded over *both* tp and fsdp (ZeRO-3-equivalent),
  optimizer states included;
* batch axes — activations are batch-sharded over ("pod","data").

Rules are name+shape driven so the same engine covers dense LMs, MLA, MoE
(EP when n_experts divides tp, intra-expert TP otherwise), GNN (replicated
weights, node/edge-sharded data) and recsys (row-sharded tables).

Also home to the **search-corpus placement** used by the sharded beam engine
(``repro.core.beam.sharded_greedy_search``): ``shard_corpus`` splits the
corpus into contiguous equal blocks (zero-padded when the row count does not
divide), ``search_mesh`` builds the 1-D device mesh the engine's
``shard_map`` program runs over.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Pytree = Any

SEARCH_AXIS = "shard"  # default mesh axis name for the sharded beam engine


def shard_corpus(corpus: jax.Array, n_shards: int) -> tuple[jax.Array, int]:
    """Contiguous-block corpus placement for the sharded search engine.

    (N, dim) -> ((S, n_local, dim), n_local) with zero-row padding when
    ``n_shards`` does not divide N. Global row i lives on shard
    ``i // n_local`` at local row ``i % n_local``; pad rows sit at global
    ids >= N, which never appear in an adjacency list, so they are never
    gathered, scored, or marked in the bitmap.
    """
    n, dim = corpus.shape
    n_local = -(-n // n_shards)
    pad = n_shards * n_local - n
    if pad:
        corpus = jnp.concatenate(
            [corpus, jnp.zeros((pad, dim), corpus.dtype)])
    return corpus.reshape(n_shards, n_local, dim), n_local


def shard_corpus_view(corpus, n_shards: int, *, quantize: str | None = None):
    """Contiguous-block placement of a full CorpusView (rows + metadata).

    ``corpus`` is a raw (N, dim) array or a prebuilt
    ``repro.kernels.CorpusView`` (possibly quantized). Returns
    ``(rows, sq, inv, scales, zero_points, n_local)`` stacks shaped
    (S, n_local, dim) / (S, n_local): the per-row dequant metadata shards
    **with** the corpus blocks — same placement, nothing enters the wave
    psum. ``scales`` / ``zero_points`` are zero-width (S, 0) stacks when
    the view has no such field (raw residency, or the symmetric fp8 modes
    for ``zero_points``) so ``shard_map`` operand arity stays fixed.

    Pad rows stay inert in every residency: a raw array is zero-padded
    *before* quantization (zero rows quantize to codes that dequantize to
    exact zeros), and a prebuilt quantized view is padded with
    code 0 / scale 1 / zero-point 0, which also dequantizes to exact
    zeros — norm 0, finite inverse norm, cosine 1.0, like every pad row.
    """
    from repro.kernels.backend import NORM_EPS, CorpusView, as_corpus_view

    if isinstance(corpus, CorpusView):
        view = as_corpus_view(corpus, quantize=quantize)  # validates mode
        n, dim = view.rows.shape
        n_local = -(-n // n_shards)
        pad = n_shards * n_local - n
        rows = jnp.concatenate(
            [view.rows, jnp.zeros((pad, dim), view.rows.dtype)])
        sq = jnp.concatenate([view.sq_norms, jnp.zeros(pad, jnp.float32)])
        inv = jnp.concatenate(
            [view.inv_norms,
             jnp.full(pad, jax.lax.rsqrt(jnp.float32(NORM_EPS)))])
        scales = view.scales
        if scales is not None:
            scales = jnp.concatenate([scales, jnp.ones(pad, jnp.float32)])
        zps = view.zero_points
        if zps is not None:
            zps = jnp.concatenate([zps, jnp.zeros(pad, jnp.float32)])
    else:
        stacked, n_local = shard_corpus(corpus, n_shards)
        flat = stacked.reshape(n_shards * n_local, corpus.shape[1])
        view = as_corpus_view(flat, quantize=quantize)
        rows, sq, inv = view.rows, view.sq_norms, view.inv_norms
        scales, zps = view.scales, view.zero_points

    def stack(a):
        if a is None:
            return jnp.zeros((n_shards, 0), jnp.float32)
        return a.reshape(n_shards, n_local, *a.shape[1:])

    return (stack(rows), stack(sq), stack(inv), stack(scales), stack(zps),
            n_local)


def search_mesh(n_shards: int, axis_name: str = SEARCH_AXIS) -> Mesh:
    """1-D mesh over the first ``n_shards`` local devices."""
    devices = jax.devices()
    if len(devices) < n_shards:
        raise ValueError(
            f"shards={n_shards} needs {n_shards} devices, but this process "
            f"sees {len(devices)} {devices[0].platform} device(s)")
    return jax.make_mesh((n_shards,), (axis_name,),
                         devices=devices[:n_shards],
                         axis_types=(jax.sharding.AxisType.Auto,))

# ZeRO stage for LM params: 3 = params FSDP+TP sharded (default);
# 1 = params TP-only (replicated over data; optimizer state stays FSDP
# sharded) — trades one param all-gather per *step* for the per-layer
# fwd/bwd weight gathers. Flipped by the perf harness.
ZERO_STAGE = 3

# weight name -> role
_IN_OUT = {  # (d_in, d_out) matrices: shard d_in over fsdp, d_out over tp
    "wq", "wk", "wv", "w_gate", "w_up", "q_a", "q_b", "kv_a", "k_b", "v_b",
    "proj", "embed_head",
}
_OUT_IN = {"wo", "w_down"}  # (d_in_tp_product, d_out): tp on axis 0
_TABLES = {"embed", "item_emb", "pos_emb", "table", "linear"}  # (vocab, d)
_REPL = {"router", "bias", "cin_out"}


def _path_keys(path) -> list[str]:
    out = []
    for p in path:
        if hasattr(p, "key"):
            out.append(str(p.key))
        elif hasattr(p, "name"):
            out.append(str(p.name))
        elif hasattr(p, "idx"):
            out.append(f"[{p.idx}]")
    return out


def _divisible(dim: int, axes: tuple[str, ...] | str | None, mesh: Mesh) -> bool:
    if axes is None:
        return True
    axes = (axes,) if isinstance(axes, str) else axes
    size = int(np.prod([mesh.shape[a] for a in axes]))
    return dim % size == 0


def _maybe(dim: int, axes, mesh) -> Any:
    """Use the axes only if they divide the dim (else replicate that dim)."""
    return axes if _divisible(dim, axes, mesh) else None


def lm_param_specs(params: Pytree, mesh: Mesh, *, tp: str = "model",
                   fsdp: tuple[str, ...] = ("data",)) -> Pytree:
    """PartitionSpec pytree matching ``params`` (arrays or ShapeDtypeStructs)."""
    fsdp_t = tuple(fsdp)

    def spec_for(path, leaf) -> P:
        keys = _path_keys(path)
        name = keys[-1]
        shape = leaf.shape
        # optimizer moment scale tensors: shaped like the param with the last
        # dim reduced by the quant block — same spec, last axis replicated.
        scanned = any("blocks" in k for k in keys)
        lead = (None,) if scanned else ()
        body = shape[1:] if scanned else shape

        def build(*ax):
            ax = ax[: len(body)] + (None,) * (len(body) - len(ax))
            fixed = tuple(
                a if _divisible(d, a, mesh) else None for a, d in zip(ax, body)
            )
            return P(*(lead + fixed))

        if name in _REPL or len(body) <= 1:
            return P(*((None,) * len(shape)))
        if name in _TABLES:
            return build(tp, fsdp_t)
        if len(body) == 3 and name in ("w_gate", "w_up", "w_down"):
            # MoE expert stacks (E, a, b): EP over tp when divisible,
            # otherwise shard the wide ffn dim over tp.
            e = body[0]
            if e % mesh.shape[tp] == 0:
                if name == "w_down":
                    return build(tp, None, fsdp_t)
                return build(tp, fsdp_t, None)
            if name == "w_down":
                return build(None, tp, fsdp_t)
            return build(None, fsdp_t, tp)
        if name in _OUT_IN:
            return build(tp, fsdp_t)
        if name in _IN_OUT:
            return build(fsdp_t, tp)
        # default for unknown 2-D weights (recsys mlp "ws" lists etc.)
        if len(body) == 2:
            return build(fsdp_t, tp)
        return P(*((None,) * len(shape)))

    return jax.tree_util.tree_map_with_path(spec_for, params)


def replicated_specs(tree: Pytree) -> Pytree:
    return jax.tree.map(lambda l: P(*((None,) * len(l.shape))), tree)


def opt_state_specs(param_specs: Pytree, opt_state, params) -> Any:
    """AdamWState sharding: master/m/v follow the param spec; quantized moment
    scales get the param spec with the last axis replicated."""
    from repro.train.optimizer import AdamWState

    def moment_spec(ps: P, mm) -> Any:
        if isinstance(mm, dict):  # quantized {"q","scale"}
            scale_spec = P(*ps[:-1], None) if len(ps) else P()
            return {"q": ps, "scale": scale_spec}
        return ps

    flat_ps, treedef = jax.tree.flatten(param_specs,
                                        is_leaf=lambda x: isinstance(x, P))
    flat_m = treedef.flatten_up_to(opt_state.m)
    flat_v = treedef.flatten_up_to(opt_state.v)
    m_specs = treedef.unflatten([moment_spec(ps, mm) for ps, mm in zip(flat_ps, flat_m)])
    v_specs = treedef.unflatten([moment_spec(ps, vv) for ps, vv in zip(flat_ps, flat_v)])
    return AdamWState(step=P(), master=param_specs, m=m_specs, v=v_specs)


def to_named(specs: Pytree, mesh: Mesh) -> Pytree:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel axes present in this mesh (pod first if any)."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def all_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data", "model") if a in mesh.shape)


# --------------------------------------------------------------------------
# activation sharding constraints
#
# GSPMD propagation alone picks contraction-sharded matmuls against FSDP
# weights (replicating activations over the batch axes — catastrophic).
# Model code calls ``constrain_batch`` at layer boundaries; launchers opt in
# by installing the mesh here. When no mesh is installed (CPU tests) the
# calls are no-ops.
# --------------------------------------------------------------------------
_ACT_CTX: dict = {"mesh": None, "dp": ()}


class activation_mesh:
    """Context manager: install the mesh used for activation constraints."""

    def __init__(self, mesh: Mesh | None, dp: tuple[str, ...] = ()):
        self.new = (mesh, tuple(dp) or (batch_axes(mesh) if mesh else ()))

    def __enter__(self):
        self.old = (_ACT_CTX["mesh"], _ACT_CTX["dp"])
        _ACT_CTX["mesh"], _ACT_CTX["dp"] = self.new
        return self

    def __exit__(self, *exc):
        _ACT_CTX["mesh"], _ACT_CTX["dp"] = self.old
        return False


def constrain(x, spec: P):
    """with_sharding_constraint against the installed mesh (no-op if none)."""
    mesh = _ACT_CTX["mesh"]
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def constrain_batch(x, *, batch_dim: int = 0):
    """Pin dim ``batch_dim`` to the data-parallel axes (if they divide it)."""
    mesh = _ACT_CTX["mesh"]
    dp = _ACT_CTX["dp"]
    if mesh is None or not dp:
        return x
    total = int(np.prod([mesh.shape[a] for a in dp]))
    if x.shape[batch_dim] % total != 0:
        return x
    spec = [None] * x.ndim
    spec[batch_dim] = dp if len(dp) > 1 else dp[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain_axis(x, dim: int, axes: tuple[str, ...] = ("model",)):
    """Pin dim ``dim`` of ``x`` to the given mesh axes. A sharding constraint
    is *total* (None = replicated), so when ``dim != 0`` the leading batch
    dim is co-pinned to the dp axes (if they divide it) — otherwise the
    constraint would silently force batch replication."""
    mesh = _ACT_CTX["mesh"]
    if mesh is None:
        return x
    axes = tuple(a for a in axes if a in mesh.shape)
    if not axes:
        return x
    total = int(np.prod([mesh.shape[a] for a in axes]))
    if x.shape[dim] % total != 0:
        return x
    spec = [None] * x.ndim
    spec[dim] = axes if len(axes) > 1 else axes[0]
    dp = tuple(a for a in _ACT_CTX["dp"] if a not in axes)
    if dim != 0 and dp:
        dp_total = int(np.prod([mesh.shape[a] for a in dp]))
        if x.shape[0] % dp_total == 0:
            spec[0] = dp if len(dp) > 1 else dp[0]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain_moe_buf(x, expert_parallel: bool):
    """(G, E, C, d) grouped dispatch buffers: G→dp axes; E→"model" when EP;
    otherwise C→"model" (intra-expert-TP archs whose E doesn't divide)."""
    mesh = _ACT_CTX["mesh"]
    dp = _ACT_CTX["dp"]
    if mesh is None:
        return x
    g, e, c = x.shape[0], x.shape[1], x.shape[2]
    spec = [None] * x.ndim
    if dp:
        total = int(np.prod([mesh.shape[a] for a in dp]))
        if g % total == 0:
            spec[0] = dp if len(dp) > 1 else dp[0]
    if "model" in mesh.shape:
        m = mesh.shape["model"]
        if expert_parallel and e % m == 0:
            spec[1] = "model"
        elif c % m == 0:
            spec[2] = "model"
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))


def constrain_seq(x, *, batch_dim: int = 0, seq_dim: int = 1,
                  seq_axes: tuple[str, ...] = ("model",)):
    """Megatron-style sequence parallelism for the residual stream: batch on
    the dp axes AND sequence on ``seq_axes``. This is what keeps the
    per-layer activation stash (the remat carry) sharded 256-ways instead of
    16-ways — see EXPERIMENTS.md §Perf."""
    mesh = _ACT_CTX["mesh"]
    dp = _ACT_CTX["dp"]
    if mesh is None:
        return x
    spec = [None] * x.ndim
    if dp:
        total = int(np.prod([mesh.shape[a] for a in dp]))
        if x.shape[batch_dim] % total == 0:
            spec[batch_dim] = dp if len(dp) > 1 else dp[0]
    axes = tuple(a for a in seq_axes if a in mesh.shape)
    if axes:
        total = int(np.prod([mesh.shape[a] for a in axes]))
        if x.shape[seq_dim] % total == 0:
            spec[seq_dim] = axes if len(axes) > 1 else axes[0]
    if all(s is None for s in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec)))
