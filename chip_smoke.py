"""Bring-up smoke test: the served bi-metric search on a TPU.

    python chip_smoke.py            # one chip: the served path, pallas vs ref
    python chip_smoke.py --chips 4  # shards=4 vs shards=1 over a 4-chip mesh

Drives the main path once through the entry points a user calls —
``BiMetricEngine(...)`` builds the index with the cheap tower only, then
``engine.submit(SearchRequest)`` → ``ServeFuture.result()`` — at the paper's
tower widths (``configs/bimetric_paper.py``): the cheap 3-layer d384 tower in
full, the expensive d4096 tower in bf16 cut to ``EXPENSIVE_LAYERS`` of its 32
layers so its weights fit one 16 GB chip. Weights and the token corpus are
made from ``--seed``.

With no option the script checks, and fails on any miss:

* JAX's first device is a TPU and ``backend="auto"`` resolved to the
  compiled (not interpreted) Pallas kernels;
* the Pallas scoring tile (plain, matmul-form, int8, shard-local) and the
  bitonic pool merge agree with the ``kernels/ref.py`` oracles on the chip;
* every request resolves, none degraded, no tower failure, and
  ``D_calls <= quota``;
* each reported stage-2 distance equals ``‖E(doc) − E(q)‖`` recomputed with
  the expensive tower, to bf16 tolerance;
* mean top-10 overlap with the same requests served by ``backend="ref"`` is
  at least ``MIN_OVERLAP``.

``--chips 4`` serves the same requests at ``shards=4`` and at ``shards=1``
and checks that ids, distances and ``D_calls`` are equal, on a mesh of 4
distinct devices.

The timings printed are bring-up timings (compilation included), not
benchmark numbers. The script exits non-zero and prints no ``ok`` line when
JAX finds no TPU or any check fails; the last line of a passing run is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

N_DOCS = 32768
DOC_LEN = 256
VOCAB = 32768
N_REQUESTS = 16
QUOTA = 128
K = 10
SLOTS = 8
EXPENSIVE_LAYERS = 8  # of the expensive tower's 32
MIN_OVERLAP = 0.9
RESULT_TIMEOUT_S = 600.0


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke: FAILED: {what}")


class Phases:
    """Wall time per phase, printed as it ends."""

    def __init__(self):
        self.seconds: dict[str, float] = {}

    def run(self, name, fn, *args, **kwargs):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        self.seconds[name] = time.perf_counter() - t0
        log(f"phase {name}: {self.seconds[name]:.2f} s")
        return out


def make_towers(seed: int, expensive_layers: int):
    """(cheap, expensive) EmbedTowers with random weights from ``seed``."""
    from repro.configs import bimetric_paper
    from repro.models import transformer as T
    from repro.serve import EmbedTower

    cheap_cfg = bimetric_paper.cheap_tower()
    exp_cfg = dataclasses.replace(bimetric_paper.expensive_tower(),
                                  n_layers=expensive_layers)
    key = jax.random.PRNGKey(seed)
    towers = []
    for i, cfg in enumerate((cheap_cfg, exp_cfg)):
        params = jax.jit(lambda k, cfg=cfg: T.init_params(k, cfg))(
            jax.random.fold_in(key, i))
        towers.append(EmbedTower(jax.block_until_ready(params), cfg))
    return tuple(towers)


def make_data(seed: int, n_docs: int, doc_len: int, vocab: int,
              n_requests: int):
    """Seeded token corpus and query rows: each query is a corpus document
    with half of its tokens replaced, so it has a near neighbour."""
    rng = np.random.default_rng(seed)
    corpus = rng.integers(0, vocab, (n_docs, doc_len), dtype=np.int32)
    src = rng.choice(n_docs, n_requests, replace=False)
    queries = corpus[src].copy()
    queries[:, : doc_len // 2] = rng.integers(
        0, vocab, (n_requests, doc_len // 2), dtype=np.int32)
    return corpus, queries


def serve(engine, queries, quota: int, k: int):
    """Submit every request, then wait for every future."""
    from repro.serve import SearchRequest

    futures = [engine.submit(SearchRequest(tokens=q, quota=quota, k=k))
               for q in queries]
    return [f.result(timeout=RESULT_TIMEOUT_S) for f in futures]


def check_served(engine, results, quota: int) -> None:
    counters = engine.counters()
    log(f"served {len(results)} requests: D_calls "
        f"{[r.stats.D_calls for r in results]}, counters {counters}")
    check(all(not r.stats.degraded for r in results) and counters.degraded == 0,
          "a request resolved degraded")
    check(counters.tower_failures == 0,
          f"{counters.tower_failures} expensive-tower failures")
    check(all(r.stats.D_calls <= quota for r in results),
          f"D_calls over the quota of {quota}")
    check(all(len(r.ids) > 0 for r in results), "a request returned no ids")


def check_stage2_dists(expensive, corpus, queries, results) -> float:
    """Reported distances vs ‖E(doc) − E(q)‖ recomputed with the tower."""
    ids = np.concatenate([r.ids for r in results])
    e_docs = expensive.embed(corpus[ids]).astype(np.float64)
    e_q = expensive.embed(queries).astype(np.float64)
    owner = np.repeat(np.arange(len(results)), [len(r.ids) for r in results])
    want = np.linalg.norm(e_docs - e_q[owner], axis=1)
    got = np.concatenate([r.dists for r in results]).astype(np.float64)
    err = float(np.max(np.abs(got - want)))
    log(f"stage-2 distances vs the recomputed tower: max |diff| {err:.3g}")
    check(np.allclose(got, want, rtol=1e-2, atol=1e-2),
          f"stage-2 distances differ from the tower by up to {err:.3g}")
    return err


def check_kernels(corpus_emb) -> None:
    """Pallas tiles vs the ref oracles at the served width, on the chip."""
    from repro.kernels import l2_topk, ops, ref

    n, dim = corpus_emb.shape
    key = jax.random.PRNGKey(7)
    qs = jax.random.normal(key, (SLOTS, dim), jnp.float32)
    ids = jax.random.randint(jax.random.fold_in(key, 1), (SLOTS, 16), -1, n)
    valid = np.asarray(ids) >= 0
    for quant in (None, "int8"):
        view = ops.as_corpus_view(corpus_emb, quantize=quant)
        if quant is None:
            want = ref.gather_score_ref(corpus_emb, qs, ids, metric="l2")
            forms = {"plain": None, "matmul": l2_topk.pack_row_meta(view)}
        else:
            want = ref.gather_score_quant_ref(
                view.rows, view.scales, view.zero_points, qs, ids,
                metric="l2")
            forms = {"int8": l2_topk.pack_row_meta(view)}
        for name, meta in forms.items():
            got = l2_topk.gather_score(view.rows, qs, ids, metric="l2",
                                       norms=meta)
            err = float(jnp.max(jnp.abs(got - want)[valid]))
            log(f"kernel gather_score[{name}] vs ref: max |diff| {err:.3g}")
            check(np.allclose(np.asarray(got)[valid], np.asarray(want)[valid],
                              rtol=1e-4, atol=1e-4)
                  and np.isinf(np.asarray(got)[~valid]).all(),
                  f"gather_score[{name}] disagrees with the oracle")
    n_local = n // 4
    offset = jnp.int32(n_local)
    got = l2_topk.gather_score_local(corpus_emb[n_local:2 * n_local], qs, ids,
                                     offset, metric="l2")
    want = ref.gather_score_local_ref(corpus_emb[n_local:2 * n_local], qs,
                                      ids, offset, metric="l2")
    check(np.allclose(np.asarray(got), np.asarray(want), rtol=1e-4,
                      atol=1e-4), "gather_score_local disagrees with the oracle")
    log("kernel gather_score_local vs ref: ok")
    pool, fan = 128, 16
    pd = jnp.sort(jax.random.uniform(jax.random.fold_in(key, 2),
                                     (SLOTS, pool)), axis=1)
    pi = jnp.arange(SLOTS * pool, dtype=jnp.int32).reshape(SLOTS, pool)
    pf = jax.random.bernoulli(jax.random.fold_in(key, 3), 0.5, (SLOTS, pool))
    cd = jax.random.uniform(jax.random.fold_in(key, 4), (SLOTS, fan))
    ci = 10_000 + jnp.arange(SLOTS * fan, dtype=jnp.int32).reshape(SLOTS, fan)
    got = ops.merge_pool_batch(pi, pd, pf, ci, cd, backend="pallas")
    want = ref.merge_pool_batch_ref(pi, pd, pf, ci, cd)
    check(all(np.array_equal(np.asarray(a), np.asarray(b))
              for a, b in zip(got, want)),
          "merge_pool_batch disagrees with the oracle")
    log("kernel merge_pool_batch vs ref: ok")


def build_engine(cheap, expensive, corpus, **kw):
    from repro.serve import BiMetricEngine

    return BiMetricEngine(cheap, expensive, corpus, slots=SLOTS, **kw)


def one_chip(args, phases: Phases) -> None:
    cheap, expensive = phases.run("towers", make_towers, args.seed,
                                  EXPENSIVE_LAYERS)
    corpus, queries = phases.run("data", make_data, args.seed, N_DOCS,
                                 DOC_LEN, VOCAB, N_REQUESTS)
    engine = phases.run("index_build[auto]", build_engine, cheap, expensive,
                        corpus, backend="auto")
    be = engine.backend
    log(f"backend='auto' resolved to {be.name} (interpret={be.interpret})")
    check(be.name == "pallas" and not be.interpret,
          f"backend 'auto' resolved to {be}, not compiled pallas")
    phases.run("kernel_parity", check_kernels, engine.emb_d)
    try:
        res = phases.run("serve[auto]", serve, engine, queries, QUOTA, K)
        check_served(engine, res, QUOTA)
    finally:
        engine.close()
    phases.run("stage2_check", check_stage2_dists, expensive, corpus,
               queries, res)
    ref_engine = phases.run("index_build[ref]", build_engine, cheap,
                            expensive, corpus, backend="ref")
    try:
        res_ref = phases.run("serve[ref]", serve, ref_engine, queries, QUOTA,
                             K)
        check_served(ref_engine, res_ref, QUOTA)
    finally:
        ref_engine.close()
    overlap = float(np.mean([
        len(set(a.ids[:K].tolist()) & set(b.ids[:K].tolist())) / K
        for a, b in zip(res, res_ref)]))
    log(f"mean top-{K} overlap, pallas vs ref: {overlap:.4f}")
    check(overlap >= MIN_OVERLAP,
          f"top-{K} overlap {overlap:.4f} < {MIN_OVERLAP}")


def four_chips(args, phases: Phases) -> None:
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX sees "
          f"{len(devices)}")
    cheap, expensive = phases.run("towers", make_towers, args.seed,
                                  EXPENSIVE_LAYERS)
    corpus, queries = phases.run("data", make_data, args.seed, N_DOCS,
                                 DOC_LEN, VOCAB, N_REQUESTS)
    runs = {}
    for shards in (4, 1):
        engine = phases.run(f"index_build[shards={shards}]", build_engine,
                            cheap, expensive, corpus, backend="auto",
                            shards=shards)
        check(engine.backend.name == "pallas"
              and not engine.backend.interpret,
              f"backend 'auto' resolved to {engine.backend}")
        if shards > 1:
            mesh_ids = sorted({d.id for d in engine._mesh.devices.flat})
            log(f"shards=4 mesh devices: {mesh_ids}")
            check(len(mesh_ids) == 4, f"mesh spans devices {mesh_ids}, "
                  "not 4 distinct devices")
        try:
            runs[shards] = phases.run(f"serve[shards={shards}]", serve,
                                      engine, queries, QUOTA, K)
            check_served(engine, runs[shards], QUOTA)
        finally:
            engine.close()
    for i, (a, b) in enumerate(zip(runs[4], runs[1])):
        check(np.array_equal(a.ids, b.ids), f"request {i}: ids differ")
        check(np.array_equal(a.dists, b.dists),
              f"request {i}: distances differ")
        check(a.stats.D_calls == b.stats.D_calls,
              f"request {i}: D_calls differ")
    log(f"shards=4 == shards=1 bit-exact on all {len(runs[1])} requests "
        "(ids, distances, D_calls)")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{len(devices)} {dev.platform} device(s)")
    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}")
    log(f"expensive tower cut to {EXPENSIVE_LAYERS} of 32 layers "
        "(full width, bf16)")
    log("timings below are bring-up timings, compilation included — not "
        "benchmark numbers")
    phases = Phases()
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args, phases)
    log(f"total: {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
