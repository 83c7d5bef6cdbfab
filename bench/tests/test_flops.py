"""``row_flops`` against XLA's own count of the tower program.

XLA's ``cost_analysis()`` of ``embed_pool`` reads higher than
``row_flops`` by the attention it computes and then masks: the program's
``blockwise_attention`` pads the key axis up to ``block_kv`` (512) and
scores every (query, key) pair of the block, where ``row_flops`` counts
the ``seq·(seq+1)/2`` pairs a causal mask keeps. With that excess taken
out, the two agree to within the elementwise operations XLA also counts
(4–6% of the excess at these sizes). XLA counts the body of the layer
scan once, so the comparison is made at one layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from harness import flops, model


@pytest.mark.parametrize("seq", [16, 64, 256])
def test_row_flops_matches_xla_but_masked_attention(seq):
    from repro.models import transformer as T

    t = dict(n_layers=1, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
             d_ff=512, vocab=1000, embed_dim=128, rope_theta=1e4,
             dtype="float32")
    rows, block_kv = 8, 512
    cfg = model.program_config(t, "t")
    params = jax.eval_shape(model.params_fn(t), jax.random.key(0))
    compiled = jax.jit(lambda p, x: T.embed_pool(p, x, cfg)).lower(
        params, jax.ShapeDtypeStruct((rows, seq), jnp.int32)).compile()
    xla = compiled.cost_analysis()["flops"]
    mine = rows * flops.row_flops(t, seq)
    per_pair = 2 * 2 * t["n_heads"] * t["head_dim"]
    masked = rows * per_pair * (seq * block_kv - seq * (seq + 1) / 2)
    assert 1.0 <= (xla - mine) / masked <= 1.1


def test_expensive_tower_row():
    """The 8-layer expensive tower at 256 tokens: 897.6 GFLOP a row."""
    t = dict(n_layers=8, d_model=4096, n_heads=32, n_kv_heads=8,
             head_dim=128, d_ff=14336, embed_dim=4096)
    assert flops.row_flops(t, 256) == pytest.approx(8.976e11, rel=1e-3)
