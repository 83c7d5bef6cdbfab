"""The dense grouped-query-attention tower: the module of a tower group that
names no ``tower``.

Its sizes (``KEYS``): ``n_layers``, ``d_model``, ``n_heads``,
``n_kv_heads``, ``head_dim``, ``d_ff``, ``vocab``, ``embed_dim``,
``rope_theta``, ``dtype``.

Weight layout (what ``repro.models.transformer.embed_pool`` reads):
``embed`` (vocab, d) ~ N(0, 0.02²); ``dense_blocks`` stacked over layers
with ``attn.{wq,wk,wv,wo}`` and ``ffn.{w_gate,w_up,w_down}`` ~ N(0, 1/d_in)
and RMS-norm gains ``ln1``, ``ln2`` = 1; ``final_norm`` = 1;
``embed_head`` (d, embed_dim) ~ N(0, 1/d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
        "d_ff", "vocab", "embed_dim", "rope_theta", "dtype")


def params_fn(t: dict):
    """``key -> params`` for tower sizes ``t`` (traceable)."""
    dtype = jnp.dtype(t["dtype"])
    d, h, hk, hd = t["d_model"], t["n_heads"], t["n_kv_heads"], t["head_dim"]
    f, n_l = t["d_ff"], t["n_layers"]

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype))

    def build(key):
        ks = jax.random.split(key, 10)
        lay = (n_l,)
        blocks = {
            "attn": {
                "wq": normal(ks[0], lay + (d, h * hd), d ** -0.5),
                "wk": normal(ks[1], lay + (d, hk * hd), d ** -0.5),
                "wv": normal(ks[2], lay + (d, hk * hd), d ** -0.5),
                "wo": normal(ks[3], lay + (h * hd, d), (h * hd) ** -0.5),
            },
            "ffn": {
                "w_gate": normal(ks[4], lay + (d, f), d ** -0.5),
                "w_up": normal(ks[5], lay + (d, f), d ** -0.5),
                "w_down": normal(ks[6], lay + (f, d), f ** -0.5),
            },
            "ln1": jnp.ones(lay + (d,), dtype),
            "ln2": jnp.ones(lay + (d,), dtype),
        }
        return {
            "embed": normal(ks[7], (t["vocab"], d), 0.02),
            "final_norm": jnp.ones((d,), dtype),
            "dense_blocks": blocks,
            "embed_head": normal(ks[8], (d, t["embed_dim"]), d ** -0.5),
        }

    return build


def program_config(t: dict, name: str):
    """The program's ``TransformerConfig`` for these sizes."""
    from repro.models.transformer import TransformerConfig

    return TransformerConfig(
        name=name, n_layers=t["n_layers"], d_model=t["d_model"],
        n_heads=t["n_heads"], n_kv_heads=t["n_kv_heads"],
        head_dim=t["head_dim"], d_ff=t["d_ff"], vocab=t["vocab"],
        rope_theta=float(t["rope_theta"]), embed_dim=t["embed_dim"],
        dtype=jnp.dtype(t["dtype"]))


def row_flops(t: dict, seq: int) -> float:
    """The multiply-adds (as 2 operations each) that embedding one row of
    ``seq`` tokens needs: per layer the q, k, v and output projections, the
    causal attention scores and weighted sum over the ``seq·(seq+1)/2``
    query-key pairs a causal mask leaves, and the three SwiGLU products;
    then the embedding head on the pooled row. Norms, rotary positions,
    softmax and pooling are elementwise and left out.

    XLA's ``cost_analysis()`` of the tower program reads higher by the
    attention it computes and then masks: the full ``seq × block`` score
    blocks, with the key axis padded up to ``block_kv``
    (``bench/tests/test_flops.py`` states the difference)."""
    d, h, hk, hd = t["d_model"], t["n_heads"], t["n_kv_heads"], t["head_dim"]
    proj = 2 * seq * d * (2 * h * hd + 2 * hk * hd)
    attn = 2 * 2 * h * hd * seq * (seq + 1) / 2
    ffn = 3 * 2 * seq * d * t["d_ff"]
    return float(t["n_layers"] * (proj + attn + ffn) + 2 * d * t["embed_dim"])
