"""Plain float32 reference of the latent-attention tower in
``tiny_mla.py``, the plain reference its test configuration names.

Straightforward ``jax.numpy`` at ``highest`` matmul precision, nothing
imported from the program: token embedding; per layer, pre-RMS-norm
multi-head latent attention (the query through its low-rank ``q_a`` with
an RMS norm, then ``q_b``; keys and values from one RMS-normed latent of
``kv_lora_rank``, a rotary key part of ``qk_rope_dim`` shared by all
heads; rotary positions on the two halves of each rotary part, causal
mask, softmax scaled by ``1/sqrt(nope + rope)``) and a SwiGLU
feed-forward, each added to the residual stream; a final RMS norm; the
mean over all positions; a linear head; an L2 normalization. It reads the
benchmark's weight arrays and upcasts them to float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, gamma, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gamma


def _rope(x, theta):
    """x (b, s, heads, dim): rotary positions on its two halves."""
    s, dim = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(x, lp, t):
    b, s, _ = x.shape
    h, nope, rope = t["n_heads"], t["qk_nope_dim"], t["qk_rope_dim"]
    a = lp["attn"]
    hn = _rms(x, lp["ln1"])
    q = (_rms(hn @ a["q_a"], a["q_a_norm"]) @ a["q_b"]).reshape(
        b, s, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:],
                                              t["rope_theta"])], -1)
    kv = hn @ a["kv_a"]
    c = _rms(kv[..., :t["kv_lora_rank"]], a["kv_a_norm"])
    k_rope = _rope(kv[..., None, t["kv_lora_rank"]:], t["rope_theta"])
    k = jnp.concatenate([(c @ a["k_b"]).reshape(b, s, h, nope),
                         jnp.broadcast_to(k_rope, (b, s, h, rope))], -1)
    v = (c @ a["v_b"]).reshape(b, s, h, t["v_head_dim"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(nope + rope)
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, -1)
    x = x + o @ a["wo"]
    hn = _rms(x, lp["ln2"])
    f = lp["ffn"]
    return x + (jax.nn.silu(hn @ f["w_gate"]) * (hn @ f["w_up"])) @ f["w_down"]


def embed(params: dict, tokens: np.ndarray, t: dict, *,
          precision: str = "float32") -> np.ndarray:
    """(n, seq) tokens -> (n, embed_dim) unit embeddings, float64 on host.
    Only the float32 reference: this tower has no control."""
    if precision != "float32":
        raise ValueError(f"no {precision!r} control for this tower")
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        x = p["embed"][jnp.asarray(tokens)]
        for layer in range(t["n_layers"]):
            x = _layer(x, jax.tree.map(lambda a: a[layer],
                                       p["dense_blocks"]), t)
        pooled = _rms(x, p["final_norm"]).mean(axis=1) @ p["embed_head"]
    pooled = pooled / jnp.sqrt((pooled * pooled).sum(-1, keepdims=True)
                               + 1e-9)
    return np.asarray(pooled, np.float64)
