"""Serving launcher: bi-metric search with model-backed metrics.

``python -m repro.launch.serve --corpus 512 --quota 48``

Builds the cheap/expensive towers (smoke sizes by default), indexes a
synthetic token corpus with the cheap tower only, then serves batched
queries under an exact expensive-model call budget, comparing the paper's
two-stage search against the re-rank baseline.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import bimetric_paper, qwen3_0_6b
from repro.launch.compile_cache import use_compile_cache
from repro.serve.engine import BiMetricEngine, EmbedTower, SearchRequest


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--corpus", type=int, default=256)
    ap.add_argument("--queries", type=int, default=8)
    ap.add_argument("--quota", type=int, default=32)
    ap.add_argument("--seq", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()

    key = jax.random.PRNGKey(0)
    cheap_cfg = qwen3_0_6b.smoke()
    exp_cfg = bimetric_paper.cheap_tower_smoke()  # stand-in big tower (CPU)
    exp_cfg = exp_cfg.__class__(**{**exp_cfg.__dict__, "n_layers": 4,
                                   "d_model": 128, "n_heads": 8,
                                   "n_kv_heads": 8, "head_dim": 16,
                                   "d_ff": 256, "embed_dim": 64,
                                   "name": "expensive-smoke"})
    from repro.models import transformer as T

    cheap = EmbedTower(T.init_params(key, cheap_cfg), cheap_cfg)
    expensive = EmbedTower(T.init_params(jax.random.fold_in(key, 1), exp_cfg),
                           exp_cfg)
    rng = np.random.default_rng(0)
    corpus = rng.integers(0, cheap_cfg.vocab, (args.corpus, args.seq),
                          dtype=np.int32)
    t0 = time.time()
    engine = BiMetricEngine(cheap, expensive, corpus)
    print(f"indexed {args.corpus} docs with the cheap tower in "
          f"{time.time()-t0:.1f}s (zero expensive calls)")

    # ground truth under D for evaluation
    emb_D = expensive.embed(corpus)
    for qi in range(args.queries):
        q = corpus[rng.integers(0, args.corpus)].copy()
        q[: args.seq // 2] = rng.integers(0, cheap_cfg.vocab, args.seq // 2)
        q_emb = expensive.embed(q[None])[0]
        true10 = np.argsort(np.linalg.norm(emb_D - q_emb, axis=1))[:10]
        res = engine.query(SearchRequest(tokens=q, quota=args.quota))
        ids_r, _, st_r = engine.rerank_query(q, quota=args.quota)
        rec_b = len(set(res.ids) & set(true10)) / 10
        rec_r = len(set(ids_r) & set(true10)) / 10
        print(f"q{qi}: bimetric recall@10={rec_b:.2f} "
              f"(D calls {res.stats.D_calls}) "
              f"| rerank recall@10={rec_r:.2f} (D calls {st_r.D_calls})")


if __name__ == "__main__":
    main()
