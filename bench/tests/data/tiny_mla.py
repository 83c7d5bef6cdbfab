"""A latent-attention tower for the tests: DeepSeek-style multi-head latent
attention with a low-rank query (``q_lora``) and a SwiGLU feed-forward in
every layer, as the program's ``repro.models.transformer`` runs it.

A test copies this file into a benchmark's ``configs/`` beside its
configuration, which names it (``"tower": "tiny_mla"``): a tower module
that the harness finds by name.

Its sizes (``KEYS``): ``n_layers``, ``d_model``, ``n_heads``,
``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_dim``, ``qk_rope_dim``,
``v_head_dim``, ``d_ff``, ``vocab``, ``embed_dim``, ``rope_theta``,
``dtype``.

Weight layout: ``embed`` (vocab, d) ~ N(0, 0.02²); ``dense_blocks``
stacked over layers with ``attn.{q_a, q_b, kv_a, k_b, v_b, wo}`` and
``ffn.{w_gate, w_up, w_down}`` ~ N(0, 1/d_in), and RMS-norm gains
``attn.q_a_norm``, ``attn.kv_a_norm``, ``ln1``, ``ln2`` = 1;
``final_norm`` = 1; ``embed_head`` (d, embed_dim) ~ N(0, 1/d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

KEYS = ("n_layers", "d_model", "n_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_dim", "qk_rope_dim", "v_head_dim", "d_ff", "vocab",
        "embed_dim", "rope_theta", "dtype")


def params_fn(t: dict):
    """``key -> params`` for tower sizes ``t`` (traceable)."""
    dtype = jnp.dtype(t["dtype"])
    d, h, f, n_l = t["d_model"], t["n_heads"], t["d_ff"], t["n_layers"]
    ql, kvl = t["q_lora_rank"], t["kv_lora_rank"]
    nope, rope, v = t["qk_nope_dim"], t["qk_rope_dim"], t["v_head_dim"]

    def normal(k, shape):
        return (jax.random.normal(k, shape, dtype)
                * jnp.asarray(shape[-2] ** -0.5, dtype))

    def build(key):
        ks = jax.random.split(key, 12)
        lay = (n_l,)
        blocks = {
            "attn": {
                "q_a": normal(ks[0], lay + (d, ql)),
                "q_a_norm": jnp.ones(lay + (ql,), dtype),
                "q_b": normal(ks[1], lay + (ql, h * (nope + rope))),
                "kv_a": normal(ks[2], lay + (d, kvl + rope)),
                "kv_a_norm": jnp.ones(lay + (kvl,), dtype),
                "k_b": normal(ks[3], lay + (kvl, h * nope)),
                "v_b": normal(ks[4], lay + (kvl, h * v)),
                "wo": normal(ks[5], lay + (h * v, d)),
            },
            "ffn": {
                "w_gate": normal(ks[6], lay + (d, f)),
                "w_up": normal(ks[7], lay + (d, f)),
                "w_down": normal(ks[8], lay + (f, d)),
            },
            "ln1": jnp.ones(lay + (d,), dtype),
            "ln2": jnp.ones(lay + (d,), dtype),
        }
        return {
            "embed": (jax.random.normal(ks[9], (t["vocab"], d), dtype)
                      * jnp.asarray(0.02, dtype)),
            "final_norm": jnp.ones((d,), dtype),
            "dense_blocks": blocks,
            "embed_head": normal(ks[10], (d, t["embed_dim"])),
        }

    return build


def program_config(t: dict, name: str):
    """The program's ``TransformerConfig``: MLA with ``q_lora``, no MoE."""
    from repro.models.transformer import TransformerConfig

    return TransformerConfig(
        name=name, n_layers=t["n_layers"], d_model=t["d_model"],
        n_heads=t["n_heads"], n_kv_heads=t["n_heads"],
        head_dim=t["qk_nope_dim"] + t["qk_rope_dim"], d_ff=t["d_ff"],
        vocab=t["vocab"], rope_theta=float(t["rope_theta"]),
        embed_dim=t["embed_dim"], dtype=jnp.dtype(t["dtype"]), mla=True,
        q_lora_rank=t["q_lora_rank"], kv_lora_rank=t["kv_lora_rank"],
        qk_nope_dim=t["qk_nope_dim"], qk_rope_dim=t["qk_rope_dim"],
        v_head_dim=t["v_head_dim"])


def row_flops(t: dict, seq: int) -> float:
    """The multiply-adds (as 2 operations each) of one row of ``seq``
    tokens: per layer the six projections (q_a, q_b, kv_a, k_b, v_b, wo),
    the causal attention scores (over q/k heads of nope + rope) and
    weighted sum (over v heads) on the ``seq·(seq+1)/2`` pairs a causal
    mask leaves, and the three SwiGLU products; then the embedding head on
    the pooled row."""
    d, h = t["d_model"], t["n_heads"]
    ql, kvl = t["q_lora_rank"], t["kv_lora_rank"]
    nope, rope, v = t["qk_nope_dim"], t["qk_rope_dim"], t["v_head_dim"]
    proj = 2 * seq * (d * ql + ql * h * (nope + rope) + d * (kvl + rope)
                      + kvl * h * (nope + v) + h * v * d)
    attn = 2 * h * (nope + rope + v) * seq * (seq + 1) / 2
    ffn = 3 * 2 * seq * d * t["d_ff"]
    return float(t["n_layers"] * (proj + attn + ffn) + 2 * d * t["embed_dim"])
