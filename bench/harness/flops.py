"""Operations of one tower call, computed from its shapes.

``row_flops(t, seq)`` counts the multiply-adds (as 2 operations each) that
embedding one row of ``seq`` tokens needs: per layer the q, k, v and output
projections, the causal attention scores and weighted sum over the
``seq·(seq+1)/2`` query-key pairs a causal mask leaves, and the three
SwiGLU products; then the embedding head on the pooled row. Norms, rotary
positions, softmax and pooling are elementwise and left out.
XLA's ``cost_analysis()`` of the tower program reads higher by the
attention it computes and then masks: the full ``seq × block`` score
blocks, with the key axis padded up to ``block_kv``
(``bench/tests/test_flops.py`` states the difference).
"""
from __future__ import annotations


def row_flops(t: dict, seq: int) -> float:
    d, h, hk, hd = t["d_model"], t["n_heads"], t["n_kv_heads"], t["head_dim"]
    proj = 2 * seq * d * (2 * h * hd + 2 * hk * hd)
    attn = 2 * 2 * h * hd * seq * (seq + 1) / 2
    ffn = 3 * 2 * seq * d * t["d_ff"]
    return float(t["n_layers"] * (proj + attn + ffn) + 2 * d * t["embed_dim"])
