"""Tower configurations and their weights, made from the run's seed.

A configuration file (``bench/configs/<config>.json``) states the expensive
tower D at its top level and the cheap tower d as the nested group
``cheap_tower``. Each group may name its architecture, ``"tower":
"<module>"``: the file ``bench/configs/<module>.py``, found by name as the
configuration's ``reference`` is (``harness.spec``). A group that names
none is the dense grouped-query-attention tower,
``bench/configs/dense_gqa.py``. A tower module gives:

* ``KEYS``: the sizes it reads from its group;
* ``params_fn(t)``: ``key -> params``, traceable, in the layout the
  program's ``repro.models.transformer.embed_pool`` reads for this
  architecture (the module's docstring states it);
* ``program_config(t, name)``: the program's ``TransformerConfig``;
* ``row_flops(t, seq)``: the useful operations of one row of ``seq``
  tokens (``harness.flops``).

A tower's sizes ``t`` (:func:`tower_dict`) hold every key of its module's
``KEYS`` and, under ``tower``, the module's name. They reach the program's
config, the plain reference and ``serve_mfu``.

Weights are drawn here, by the benchmark, on the device in one jitted call
per tower, in the dtype the tower is served in: the program under test
receives them as inputs, and the plain reference reads the same arrays, so
neither side makes weights for the other.
"""
from __future__ import annotations

from pathlib import Path

import jax
import jax.numpy as jnp

from harness import spec

BENCH = Path(__file__).resolve().parents[1]


def tower_dict(cfg: dict, which: str, bench: Path) -> dict:
    """The tower's sizes: ``which`` is ``"expensive"`` (the file's top
    level) or ``"cheap"`` (its ``cheap_tower`` group); its module is found
    under ``bench``."""
    group = spec.tower_group(cfg, which)
    name = spec.tower_name(group)
    mod = spec.config_module(bench, name)
    return {**{k: group[k] for k in mod.KEYS}, "tower": name}


def tower_module(t: dict):
    """The tower module of sizes ``t``: the one ``t["tower"]`` names, the
    dense one where ``t`` names none."""
    return spec.config_module(BENCH, t.get("tower", spec.DENSE_TOWER))


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
    return tower_module(t).params_fn(t)


def program_config(t: dict, name: str):
    """The program's ``TransformerConfig`` for these sizes."""
    return tower_module(t).program_config(t, name)
