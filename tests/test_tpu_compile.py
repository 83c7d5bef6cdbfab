"""The search-path Pallas kernels compile for a TPU v5e at real widths.

Interpret mode (every other kernel test) cannot see the chip compiler's
tiling and VMEM rules. These tests compile each kernel ahead of time for a
described ``v5e:2x2`` topology — no chip attached — and assert that the
compiled program holds the Pallas kernel (a ``tpu_custom_call``).

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import l2_topk

SLOTS = 8  # the engine's slot count — the wave's query batch
FANOUT = 16  # one expanded vertex's out-degree (the engine's max_degree)
# corpus rows per width: the chip smoke corpus at the cheap tower's d384,
# and the 1M-row search_perf corpus at d256
CORPUS_ROWS = {384: 32768, 256: 1 << 20}


@pytest.fixture(scope="module")
def spec():
    """(shape, dtype) -> an abstract array on one described v5e chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    # a compile for a described chip cannot be read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setenv("TPU_LOG_DIR", "disabled")
            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            chip = SingleDeviceSharding(topo.devices[0])
            yield lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                         sharding=chip)
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compiled_text(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("dim", sorted(CORPUS_ROWS))
@pytest.mark.parametrize("form", ["plain", "matmul", "int8"])
def test_scoring_tile_compiles(spec, form, dim):
    n = CORPUS_ROWS[dim]
    rows_dt = jnp.int8 if form == "int8" else jnp.float32
    args = [spec((n, dim), rows_dt), spec((SLOTS, dim), jnp.float32),
            spec((SLOTS, FANOUT), jnp.int32)]
    if form != "plain":
        args.append(spec((n, 4 if form == "int8" else 2), jnp.float32))
    fn = lambda c, q, i, *m: l2_topk.gather_score(
        c, q, i, metric="l2", norms=m[0] if m else None)
    assert "tpu_custom_call" in _compiled_text(fn, *args)


def test_scoring_tile_local_compiles(spec):
    n_local = CORPUS_ROWS[384] // 4
    fn = lambda c, q, i, off, m: l2_topk.gather_score_local(
        c, q, i, off, metric="l2", norms=m)
    text = _compiled_text(
        fn, spec((n_local, 384), jnp.float32), spec((SLOTS, 384), jnp.float32),
        spec((SLOTS, FANOUT), jnp.int32), spec((), jnp.int32),
        spec((n_local, 2), jnp.float32))
    assert "tpu_custom_call" in text


# (pool, fanout): the stage-1 pool (64) with one expansion's fanout, and
# the stage-2 pool at quota 128 with a 4-wide frontier — padded networks of
# 128 and 256 lanes
@pytest.mark.parametrize("pool,fanout", [(64, FANOUT), (128, 4 * FANOUT)])
def test_pool_merge_compiles(spec, pool, fanout):
    fn = lambda pi, pd, pf, ci, cd: l2_topk.beam_merge_topk(
        pi, pd, ci, cd, beam_flags=pf)
    text = _compiled_text(
        fn, spec((SLOTS, pool), jnp.int32), spec((SLOTS, pool), jnp.float32),
        spec((SLOTS, pool), jnp.int32), spec((SLOTS, fanout), jnp.int32),
        spec((SLOTS, fanout), jnp.float32))
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(spec):
    """The expensive tower's attention shape: 32 heads of 128, bf16."""
    s = spec((1, 32, 512, 128), jnp.bfloat16)
    text = _compiled_text(
        lambda q, k, v: fa.flash_attention(q, k, v, causal=True), s, s, s)
    assert "tpu_custom_call" in text


def test_stage1_program_compiles(spec):
    """The serving engine's stage-1 program on the Pallas route, at the
    SCIDOCS-shaped corpus (25,657 rows of d384, degree 16), with the corpus
    view and the graph as arguments: one while loop holding the kernels."""
    from repro.kernels import CorpusView
    from repro.kernels.backend import Backend
    from repro.serve.engine import _METRIC_D, _stage1_j

    n, dim = 25657, 384
    view = CorpusView(rows=spec((n, dim), jnp.float32),
                      sq_norms=spec((n,), jnp.float32),
                      inv_norms=spec((n,), jnp.float32))
    text = _stage1_j.lower(
        view, spec((n, FANOUT), jnp.int32), spec((SLOTS, dim), jnp.float32),
        spec((SLOTS, 1), jnp.int32), spec((SLOTS,), jnp.int32),
        spec((SLOTS,), jnp.int32), n_points=n, pool_size=64,
        metric=_METRIC_D, backend=Backend("pallas")).compile().as_text()
    assert "tpu_custom_call" in text and "while" in text
