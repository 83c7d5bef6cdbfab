"""Plain reference of the two embedding towers (both configurations).

The published description of a Mistral-family embedding encoder, written in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision,
with no kernels, cache or batching from the program and nothing imported
from it: token embedding; per layer, pre-RMS-norm grouped-query attention
(rotary positions on the two halves of each head, causal mask, softmax)
and a SwiGLU feed-forward, each added to the residual stream; a final
RMS norm; the mean over all positions; a linear head; an L2 normalization.

The reference reads the weight arrays the benchmark made from the seed (in
the dtype they are served in) and upcasts them to float32. It runs layer by
layer, in blocks of rows, so it fits beside whatever else the process holds.

The controls, each the next precision below the one a tower is served in:
``precision="fp8"`` (below the expensive tower's bfloat16) rounds every
matmul operand to float8 e4m3, scaled per row of the activations and per
output column of the weights, before the float32 product;
``precision="bf16"`` (below the cheap tower's float32) runs the tower as a
bfloat16 one would: matmul operands and products, the residual stream,
queries, keys, values and attention weights rounded to bfloat16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_E4M3_MAX = 448.0


def _fake_fp8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / _E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x, precision):
    """``x`` as the bfloat16 control would hold it; as is otherwise."""
    if precision != "bf16":
        return x
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(x, w, precision):
    """x (..., k) @ w (k, n) in float32; the controls round the operands
    (and, for bf16, the product)."""
    if precision == "fp8":
        x, w = _fake_fp8(x, -1), _fake_fp8(w, 0)
    x, w = _bf16(x, precision), _bf16(w, precision)
    return _bf16(jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST),
                 precision)


def _rms(x, gamma, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _rope(x, theta):
    s, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("t", "precision"))
def _layer(x, lp, *, t, precision):
    t = dict(t)
    lp = jax.tree.map(lambda a: a.astype(jnp.float32), lp)
    b, s, _ = x.shape
    h, hk, hd = t["n_heads"], t["n_kv_heads"], t["head_dim"]
    a = lp["attn"]
    hn = _rms(x, lp["ln1"])
    q = _rope(_mm(hn, a["wq"], precision).reshape(b, s, h, hd),
              t["rope_theta"])
    k = _rope(_mm(hn, a["wk"], precision).reshape(b, s, hk, hd),
              t["rope_theta"])
    v = _mm(hn, a["wv"], precision).reshape(b, s, hk, hd)
    k = jnp.repeat(k, h // hk, axis=2)
    v = jnp.repeat(v, h // hk, axis=2)
    if precision == "fp8":
        q, k, v = _fake_fp8(q, -1), _fake_fp8(k, -1), _fake_fp8(v, 1)
    q, k, v = (_bf16(a, precision) for a in (q, k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    if precision == "fp8":
        p = _fake_fp8(p, -1)
    p = _bf16(p, precision)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(b, s, h * hd)
    x = _bf16(x + _mm(o, a["wo"], precision), precision)
    hn = _rms(x, lp["ln2"])
    f = lp["ffn"]
    g = jax.nn.silu(_mm(hn, f["w_gate"], precision))
    return _bf16(x + _mm(g * _mm(hn, f["w_up"], precision), f["w_down"],
                                 precision), precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, final_norm, head, *, precision):
    x = _rms(x, final_norm.astype(jnp.float32))
    pooled = _mm(x.mean(axis=1), head.astype(jnp.float32), precision)
    return pooled / jnp.sqrt((pooled * pooled).sum(-1, keepdims=True) + 1e-9)


def embed(params: dict, tokens: np.ndarray, t: dict, *,
          precision: str = "float32", block_tokens: int = 8192) -> np.ndarray:
    """(n, seq) tokens -> (n, embed_dim) unit embeddings, float64 on host."""
    n, seq = tokens.shape
    rows = max(1, block_tokens // seq)
    pad = (-n) % rows
    toks = np.pad(tokens, ((0, pad), (0, 0)))
    key = tuple(sorted(t.items()))
    emb = params["embed"]
    xs = [_bf16(emb[jnp.asarray(toks[i:i + rows])].astype(jnp.float32),
                precision) for i in range(0, len(toks), rows)]
    blocks = params["dense_blocks"]
    for layer in range(t["n_layers"]):
        lp = jax.tree.map(lambda a, i=layer: a[i], blocks)
        xs = [_layer(x, lp, t=key, precision=precision) for x in xs]
        del lp
    out = [np.asarray(_head(x, params["final_norm"], params["embed_head"],
                            precision=precision)) for x in xs]
    return np.concatenate(out)[:n].astype(np.float64)
