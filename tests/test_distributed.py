"""Multi-device tests (8 forced host devices, run in a subprocess so the
main test process keeps its single-device view)."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(body: str) -> str:
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, "src")
        import jax, jax.numpy as jnp
        import numpy as np
        from functools import partial
        from jax.sharding import PartitionSpec as P
        from jax.sharding import AxisType
        shard_map = partial(jax.shard_map, check_vma=False)
        def make_mesh(shape, axes, devices=None):
            return jax.make_mesh(shape, axes, devices=devices,
                                 axis_types=(AxisType.Auto,) * len(axes))
        mesh = make_mesh((8,), ("x",))
    """) + textwrap.dedent(body)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=ROOT, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.slow
def test_overlapped_collectives_match_dense():
    out = _run("""
        from repro.distributed import collectives as C
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (16, 12))
        w = jax.random.normal(jax.random.fold_in(key, 1), (12, 10))
        f = shard_map(partial(C.allgather_matmul, axis_name="x"),
                      mesh=mesh, in_specs=(P("x", None), P(None, None)),
                      out_specs=P(None, None))
        assert float(jnp.abs(f(x, w) - x @ w).max()) < 1e-4
        xk = jax.random.normal(key, (16, 24))
        wk = jax.random.normal(jax.random.fold_in(key, 2), (24, 10))
        g = shard_map(partial(C.matmul_reducescatter, axis_name="x"),
                      mesh=mesh, in_specs=(P(None, "x"), P("x", None)),
                      out_specs=P("x", None))
        assert float(jnp.abs(g(xk, wk) - xk @ wk).max()) < 1e-4
        print("COLLECTIVES_OK")
    """)
    assert "COLLECTIVES_OK" in out


@pytest.mark.slow
def test_gpipe_forward_backward():
    out = _run("""
        from repro.distributed import pipeline as PP
        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])
        def init_stage(k):
            return {"w": jax.random.normal(k, (16, 16)) * 0.5,
                    "b": jnp.zeros(16)}
        key = jax.random.PRNGKey(0)
        sp = PP.stack_stage_params(init_stage, key, 8)
        xm = jax.random.normal(jax.random.fold_in(key, 5), (4, 6, 16))
        def ploss(spp, xmm):
            o = shard_map(
                lambda s_, x_: PP.gpipe_apply(
                    stage_fn, jax.tree.map(lambda a: a[0], s_), x_,
                    axis_name="x", n_micro=4),
                mesh=mesh, in_specs=(P("x"), P(None)),
                out_specs=P(None))(spp, xmm)
            return (o ** 2).sum()
        def rloss(spp, xmm):
            r = xmm
            for s in range(8):
                ps = jax.tree.map(lambda a: a[s], spp)
                r = jax.vmap(lambda mb: stage_fn(ps, mb))(r)
            return (r ** 2).sum()
        assert abs(float(ploss(sp, xm)) - float(rloss(sp, xm))) < 1e-3
        g1 = jax.grad(ploss)(sp, xm)
        g2 = jax.grad(rloss)(sp, xm)
        err = max(float(jnp.abs(a - b).max())
                  for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
        assert err < 1e-4, err
        print("GPIPE_OK")
    """)
    assert "GPIPE_OK" in out


@pytest.mark.slow
def test_quantized_psum_accuracy():
    out = _run("""
        from repro.train.compression import quantized_psum
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 256))
        f = shard_map(lambda t: quantized_psum(t, "x"), mesh=mesh,
                      in_specs=P("x", None), out_specs=P("x", None))
        approx = f(g)
        exact = jnp.broadcast_to(g.sum(0, keepdims=True), (8, 256))
        rel = float(jnp.abs(approx - exact).max() / jnp.abs(exact).max())
        assert rel < 0.05, rel
        print("QPSUM_OK", rel)
    """)
    assert "QPSUM_OK" in out


@pytest.mark.slow
def test_sharded_bimetric_search_matches_quality():
    """Scatter-gather search over 4 corpus shards reaches the recall of the
    exact D ranking at a moderate budget."""
    out = _run("""
        mesh2 = make_mesh((2, 4), ("data", "model"))
        from repro.core import distances, metrics
        from repro.core.distributed import build_sharded, sharded_bimetric_search
        from repro.core.vamana import VamanaConfig
        from repro.data.synthetic import make_dataset
        data = make_dataset(n=1024, n_queries=16, dim_D=48, dim_d=8,
                            noise=0.1, seed=2)
        cfg = VamanaConfig(max_degree=12, l_build=16, pool_size=32,
                           rev_candidates=12, build_batch=256)
        idx = build_sharded(data.corpus_d, data.corpus_D, 4, cfg)
        ids, dd, calls = sharded_bimetric_search(
            mesh2, idx, data.queries_d, data.queries_D, quota=256, k=10)
        em_D = distances.EmbeddingMetric(data.corpus_D)
        true_ids, _ = em_D.brute_force(data.queries_D, 10)
        rec = float(metrics.recall_at_k(ids, true_ids).mean())
        assert rec >= 0.7, rec
        assert int(jnp.asarray(calls).max()) <= 256
        print("SHARDED_OK", rec)
    """)
    assert "SHARDED_OK" in out


@pytest.mark.slow
def test_serve_engine_sharded_stage1_parity():
    """BiMetricEngine(shards=4) answers bit-identically to the single-device
    engine — the stage-1 corpus mesh must not perturb results or budgets."""
    out = _run("""
        from repro.configs import qwen3_0_6b
        from repro.models import transformer as T
        from repro.serve import BiMetricEngine, EmbedTower
        key = jax.random.PRNGKey(0)
        cheap_cfg = qwen3_0_6b.smoke()
        exp_cfg = T.TransformerConfig(
            name="exp-smoke", n_layers=2, d_model=64, n_heads=4,
            n_kv_heads=4, head_dim=16, d_ff=128, vocab=cheap_cfg.vocab,
            embed_dim=32)
        cheap = EmbedTower(T.init_params(key, cheap_cfg), cheap_cfg)
        expensive = EmbedTower(
            T.init_params(jax.random.fold_in(key, 1), exp_cfg), exp_cfg)
        corpus = np.random.default_rng(0).integers(
            0, cheap_cfg.vocab, (96, 10), dtype=np.int32)
        qs = corpus[[3, 40, 77]].copy()
        eng1 = BiMetricEngine(cheap, expensive, corpus)
        ids1, dd1, st1 = eng1.query_batch(qs, quota=15, k=5)
        eng4 = BiMetricEngine(cheap, expensive, corpus, shards=4)
        ids4, dd4, st4 = eng4.query_batch(qs, quota=15, k=5)
        assert np.array_equal(ids1, ids4)
        np.testing.assert_array_equal(dd1, dd4)
        assert [s.d_calls for s in st1] == [s.d_calls for s in st4]
        assert [s.D_calls for s in st1] == [s.D_calls for s in st4]
        r1, rd1, _ = eng1.rerank_query_batch(qs, quota=20, k=5)
        r4, rd4, _ = eng4.rerank_query_batch(qs, quota=20, k=5)
        assert np.array_equal(r1, r4)
        np.testing.assert_array_equal(rd1, rd4)
        print("SERVE_SHARDED_OK")
    """)
    assert "SERVE_SHARDED_OK" in out


@pytest.mark.slow
def test_elastic_checkpoint_reshard(tmp_path):
    """Save on an 8-device mesh, restore onto a 4-device mesh."""
    out = _run(f"""
        from jax.sharding import NamedSharding
        from repro.checkpoint.manager import CheckpointManager
        arr = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        sh8 = NamedSharding(mesh, P("x", None))
        tree = {{"w": jax.device_put(arr, sh8)}}
        mgr = CheckpointManager("{tmp_path}", keep=2)
        mgr.save(1, tree, async_=False)
        mesh4 = make_mesh((4,), ("y",), jax.devices()[:4])
        sh4 = NamedSharding(mesh4, P(None, "y"))
        restored, _ = mgr.restore(
            {{"w": jax.ShapeDtypeStruct((8, 8), jnp.float32)}},
            sharding_for=lambda path, a: sh4)
        assert restored["w"].sharding == sh4
        assert float(jnp.abs(restored["w"] - arr).max()) == 0.0
        print("RESHARD_OK")
    """)
    assert "RESHARD_OK" in out
