"""Serving engine: exact budget, batch-vs-single parity, cache accounting.

Uses deliberately tiny towers/corpus so the whole file stays test-suite
cheap while still exercising the real path: tower embed -> cheap-only index
build -> batched stage 1 on device -> host-driven stage 2 draining the
expensive tower in batches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import qwen3_0_6b
from repro.core import beam
from repro.models import transformer as T
from repro.serve import BiMetricEngine, EmbedTower, SearchRequest
from repro.serve.engine import _METRIC_D, _stage1_j


@pytest.fixture(scope="module")
def engine_parts():
    key = jax.random.PRNGKey(0)
    cheap_cfg = qwen3_0_6b.smoke()
    exp_cfg = T.TransformerConfig(
        name="exp-smoke", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=128, vocab=cheap_cfg.vocab, embed_dim=32)
    cheap = EmbedTower(T.init_params(key, cheap_cfg), cheap_cfg)
    expensive = EmbedTower(
        T.init_params(jax.random.fold_in(key, 1), exp_cfg), exp_cfg)
    corpus = np.random.default_rng(0).integers(
        0, cheap_cfg.vocab, (96, 10), dtype=np.int32)
    return cheap, expensive, corpus


def _fresh_engine(engine_parts):
    cheap, expensive, corpus = engine_parts
    return BiMetricEngine(cheap, expensive, corpus)


def test_quota_exact_and_batch_single_parity(engine_parts):
    eng = _fresh_engine(engine_parts)
    qs = eng.corpus_tokens[[3, 40, 77]].copy()
    ids_b, dd_b, stats_b = eng.query_batch(qs, quota=15, k=5)
    assert ids_b.shape == (3, 5)
    assert all(s.D_calls <= 15 for s in stats_b)

    # per-query accounting parity: a fresh engine, one query at a time
    eng2 = _fresh_engine(engine_parts)
    for i in range(3):
        ids1, dd1, s1 = eng2.query(qs[i], quota=15, k=5)
        ok = (ids_b[i] >= 0) & np.isfinite(dd_b[i])
        assert (ids1 == ids_b[i][ok]).all()
        np.testing.assert_allclose(dd1, dd_b[i][ok], rtol=1e-5)
        assert s1.D_calls == stats_b[i].D_calls


def test_cache_saves_tower_batches_not_accounting(engine_parts):
    eng = _fresh_engine(engine_parts)
    q = eng.corpus_tokens[7]
    b0 = eng.counters().drain_batches
    ids1, dd1, s1 = eng.query(q, quota=12, k=5)
    b1 = eng.counters().drain_batches
    ids2, dd2, s2 = eng.query(q, quota=12, k=5)
    b2 = eng.counters().drain_batches
    assert (ids1 == ids2).all()
    np.testing.assert_array_equal(dd1, dd2)
    assert s1.D_calls == s2.D_calls  # budget accounting is cache-blind
    assert b2 - b1 == 0  # but the tower is not re-run
    assert b1 - b0 > 0


def test_quota_zero_spends_nothing(engine_parts):
    eng = _fresh_engine(engine_parts)
    b0 = eng.counters().drain_batches
    ids, dd, st = eng.query(eng.corpus_tokens[0], quota=0, k=5)
    assert ids.size == 0 and st.D_calls == 0
    assert eng.counters().drain_batches - b0 == 0


def test_rerank_exact_budget(engine_parts):
    eng = _fresh_engine(engine_parts)
    ids, dd, st = eng.rerank_query(eng.corpus_tokens[11], quota=16, k=5)
    assert st.D_calls <= 16
    assert (np.diff(dd) >= 0).all()


def test_dedup_backends_bit_exact(engine_parts):
    """Stage 2 on the sorted (quota-proportional) dedup state answers
    exactly what the bitmap state answers, mixed quotas included."""
    cheap, expensive, corpus = engine_parts
    qs = corpus[[3, 40, 77]].copy()
    quotas = np.array([4, 15, 9], np.int32)
    results = {}
    for dedup in ("bitmap", "sorted", "auto"):
        eng = BiMetricEngine(cheap, expensive, corpus, dedup=dedup)
        results[dedup] = eng.query_batch(qs, quota=quotas, k=5)
    ids_ref, dd_ref, st_ref = results["bitmap"]
    for dedup in ("sorted", "auto"):
        ids, dd, st = results[dedup]
        assert np.array_equal(ids, ids_ref), dedup
        np.testing.assert_array_equal(dd, dd_ref)
        assert [s.D_calls for s in st] == [s.D_calls for s in st_ref]
    assert [s.D_calls for s in st_ref] == [4, 15, 9]


def test_dedup_capacity_rounding_bounds_retraces(engine_parts):
    """The wave capacity is the max quota rounded up to a power of two —
    quota-0 padding rows never raise it, distinct quotas inside one bucket
    share one trace, and an all-quota-0 wave gets a zero-capacity set."""
    from repro.serve.engine import _round_capacity
    assert _round_capacity(0) == 0
    assert _round_capacity(1) == 1
    assert _round_capacity(5) == 8
    assert _round_capacity(8) == 8
    assert _round_capacity(9) == 16
    cheap, expensive, corpus = engine_parts
    eng = BiMetricEngine(cheap, expensive, corpus, dedup="sorted")
    # mixed wave incl. a quota-0 row (the padded-row shape) and a
    # same-bucket wave: both run the sorted backend, answers match solo runs
    ids_m, dd_m, st_m = eng.query_batch(
        corpus[[3, 40, 77]].copy(),
        quota=np.array([0, 12, 9], np.int32), k=5)
    assert st_m[0].D_calls == 0 and (ids_m[0] == -1).all()
    solo = BiMetricEngine(cheap, expensive, corpus, dedup="sorted")
    for i, q in ((1, 12), (2, 9)):
        ids1, dd1, s1 = solo.query(corpus[[3, 40, 77][i]], quota=q, k=5)
        ok = (ids_m[i] >= 0) & np.isfinite(dd_m[i])
        assert np.array_equal(ids1, ids_m[i][ok])
        assert s1.D_calls == st_m[i].D_calls


def _serve_async(eng, reqs):
    return [f.result(timeout=300) for f in [eng.submit(r) for r in reqs]]


def test_stage1_traced_once_per_shape(engine_parts):
    """Stage 1 is one jitted program per (batch, pool) bucket: a second
    same-shape admission group adds no trace, and neither does a second
    engine over another corpus of the same shape — the corpus and graph
    are operands of the program, not constants baked into it. Both drives
    still answer bit-identically."""
    cheap, expensive, corpus = engine_parts
    other = np.random.default_rng(1).integers(
        0, cheap.cfg.vocab, corpus.shape, dtype=np.int32)
    # five slots: a (batch, pool) bucket no other test of this file uses
    eng = BiMetricEngine(cheap, expensive, corpus, max_batch=5,
                         max_wait_ms=500.0)
    eng2 = BiMetricEngine(cheap, expensive, other, max_batch=5,
                          max_wait_ms=500.0)
    groups = [[SearchRequest(tokens=c[i], quota=15, k=5) for i in rows]
              for c, rows in ((corpus, (3, 40, 77)), (corpus, (11, 58, 90)),
                              (other, (5, 6, 7)))]
    try:
        t0 = eng.counters().stage1_traces
        first = _serve_async(eng, groups[0])
        t1 = eng.counters().stage1_traces
        second = _serve_async(eng, groups[1])
        assert eng.counters().stage1_traces == t1
        assert t1 - t0 <= 1
        on_other = _serve_async(eng2, groups[2])
        assert eng2.counters().stage1_traces == 0
        assert eng.counters().stage1_traces == t1
    finally:
        eng.close()
        eng2.close()
    for e, reqs, got in ((eng, groups[0], first), (eng, groups[1], second),
                         (eng2, groups[2], on_other)):
        for a, b in zip(got, e.query_batch(reqs)):
            assert np.array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.dists, b.dists)
            assert a.stats.d_calls == b.stats.d_calls
            assert a.stats.D_calls == b.stats.D_calls


@pytest.mark.parametrize("backend", ["ref", "xla_matmul"])
def test_stage1_program_matches_eager_search(engine_parts, backend):
    """``_stage1_j`` answers what the eager ``batched_greedy_search`` over
    the engine's corpus answers, on the plain and the matmul route: the
    same pools, calls and steps, distances to float32 rounding."""
    cheap, expensive, corpus = engine_parts
    eng = BiMetricEngine(cheap, expensive, corpus, backend=backend)
    q_d = jnp.asarray(cheap.embed(corpus[[3, 40, 77, 11]]))
    entries = jnp.full((4, 1), eng.index.medoid, jnp.int32)
    width = jnp.asarray([32, 1, 40, 36], jnp.int32)
    max_steps = jnp.asarray([128, 0, 160, 144], jnp.int32)
    got = _stage1_j(eng._corpus_d, eng._adjacency, q_d, entries, width,
                    max_steps, n_points=eng.n, pool_size=64,
                    metric=_METRIC_D, backend=eng.backend)
    want = beam.batched_greedy_search(
        beam.fused_dist_fn(eng._corpus_d, _METRIC_D, backend=eng.backend),
        eng._adjacency, q_d, entries, n_points=eng.n, beam_width=width,
        pool_size=64, max_steps=max_steps, backend=eng.backend)
    for field in ("pool_ids", "n_calls", "n_steps", "scored"):
        np.testing.assert_array_equal(getattr(got, field),
                                      getattr(want, field), err_msg=field)
    np.testing.assert_allclose(got.pool_dists, want.pool_dists, rtol=1e-6)
