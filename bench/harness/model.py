"""Tower configurations and their weights, made from the run's seed.

A configuration file (``bench/configs/<config>.json``) states the expensive
tower D at its top level and the cheap tower d as the nested group
``cheap_tower``. Weights are drawn here, by the benchmark, on the device in
one jitted call per tower, in the dtype the tower is served in: the program
under test receives them as inputs, and the plain reference reads the same
arrays, so neither side makes weights for the other.

Layout (what ``repro.models.transformer.embed_pool`` reads): ``embed``
(vocab, d) ~ N(0, 0.02²); ``dense_blocks`` stacked over layers with
``attn.{wq,wk,wv,wo}`` and ``ffn.{w_gate,w_up,w_down}`` ~ N(0, 1/d_in) and
RMS-norm gains ``ln1``, ``ln2`` = 1; ``final_norm`` = 1; ``embed_head``
(d, embed_dim) ~ N(0, 1/d).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

TOWER_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "embed_dim", "rope_theta", "dtype")


def tower_dict(cfg: dict, which: str) -> dict:
    """The tower's sizes: ``which`` is ``"expensive"`` (the file's top
    level) or ``"cheap"`` (its ``cheap_tower`` group)."""
    src = cfg if which == "expensive" else cfg["cheap_tower"]
    return {k: src[k] for k in TOWER_KEYS}


def seed_key(seed: int) -> jax.Array:
    """A threefry key from a seed of up to 64 bits."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.array([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32),
        impl="threefry2x32")


def make_params(key: jax.Array, t: dict) -> dict:
    """Random tower weights, on the device, in one jitted call."""
    return jax.block_until_ready(jax.jit(params_fn(t))(key))


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
