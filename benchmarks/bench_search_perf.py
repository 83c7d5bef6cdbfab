"""Search-engine throughput: retired per-query path vs the batched engine.

The "old" path is the pre-refactor implementation, frozen verbatim in
``repro.core._legacy_beam`` — a ``jax.vmap`` over single-query greedy
searches (one vertex expanded per query per step, stable-argsort merges).
The "new" path is the batched engine stepping the whole query batch through
one fixed-shape hot loop with ``expand_width`` frontier vertices per wave.

Two "old" baselines are reported, because the retired code had two shapes:

* ``old_perquery`` — the serving reality: the pre-refactor engine answered
  queries one at a time (stage 2 was a per-request host loop), so its batch
  throughput is B sequential single-query searches. The headline
  ``speedup_at_32`` is measured against this — it is what the refactor
  changes for the serving path.
* ``old_vmap`` — the pre-refactor core batch path (``jax.vmap`` of the
  single-query search), the strongest form the old engine ever had.

Two scenarios, matching the two halves of the paper's search:

* ``stage2_quota`` — the paper's cost model: quota-bounded search under the
  expensive metric D. Both paths stop at the same exact call budget, so this
  is a pure engine-efficiency comparison (equal work per query).
  ``expand_width=2`` — wider waves spend the fixed budget more greedily and
  cost recall under tight quotas.
* ``stage1_unbounded`` — convergence-bounded search under the cheap proxy d
  (no quota). Runtime depends on query difficulty, so these numbers are
  noisier; ``expand_width=6`` both raises recall and cuts steps here.

Also kernel-vs-oracle microbenches (interpret mode measures the correctness
path; on TPU the Pallas kernels replace the XLA fallbacks), and a
``sharded`` scenario: the same quota-bounded search run device-parallel at
2/4/8 devices, as many as this process sees (CI forces 8 host devices with
``--xla_force_host_platform_device_count``), parity-checked bit-exact
against the single-device engine. On a CPU host the shards share the same
cores, so this tracks collective overhead, not a real speedup — the
trajectory artifact is what CI gates on.

The ``dedup`` scenario compares the engine's two dedup-state backends at
quota 256 on a large random-graph corpus (bit-exact parity asserted):

* ``fused_loop`` — one jitted ``while_loop`` (the stage-1 / bi-metric
  shape). XLA aliases the loop carry, so the (B, N) bitmap's scatter is
  in-place and cheap; the sorted set pays an O(quota) merge per step. The
  bitmap wins this shape on CPU at small/medium N (which is why the fused
  engine's ``dedup="auto"`` keeps it); at the scenario's 1M rows the
  bitmap's O(B·N) init/materialization starts to tell and the two roughly
  tie — recorded for honesty either way.
* ``serve_drive`` — the serving engine's host-driven stage-2 plan/commit
  shape: separate jitted dispatches per step, exactly like
  ``serve.engine``. The non-donated (B, N) bitmap is round-tripped (copied)
  through every dispatch while the sorted set moves (B, quota) — this is
  the path the quota-proportional state was built for, and its
  ``speedup_at_quota_256`` is the gated headline.

The ``matmul`` scenario (see :func:`_matmul_scenario`) compares the two
wave-scoring forms behind the kernel backend knob — gather-then-reduce
(``backend="ref"``) vs MXU-form over the corpus-norm cache
(``backend="xla_matmul"``) — on a 1M-row corpus at B ∈ {1..128};
``result.matmul.speedup_at_32`` (the scoring stage at batch 32) is gated.

The ``quantized`` scenario (see :func:`_quantized_scenario`) runs the same
1M-row waves against int8 / fp8 quantized residency
(``ops.as_corpus_view(corpus, quantize=...)``): recall@10 of the lossy
scoring path against the exact-f32 ranking of the identical wave (matched
quota by construction), scoring-stage speedup, and bytes-per-row.
``result.quantized.recall_at_10`` (int8 fidelity, tolerance 0.05) and
``result.quantized.compression_int8`` (row-payload compression, >= 3.9x)
are gated.

Writes ``BENCH_search_perf.json`` (via benchmarks/run.py, or directly when
executed as a script) — the machine-readable perf trajectory artifact.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Setup, emit, write_bench_json
from repro.core import _legacy_beam, beam, distances, metrics
from repro.core.beam import batched_greedy_search, sharded_greedy_search
from repro.kernels import ops

BATCH_SIZES = (1, 8, 32, 64, 128)
BEAM = 32
K = 10
QUOTA = 128  # stage-2 scenario budget
E_QUOTA = 2  # wave width under a quota (recall-safe)
E_UNBOUNDED = 6  # wave width for convergence-bounded search
SHARD_COUNTS = (2, 4, 8)  # shard counts for the sharded scenario
SHARD_BATCH = 32
# dedup-backend scenario: a corpus big enough that the (B, N) bitmap's
# round-trips through the host-driven dispatches dominate the fixed
# dispatch cost (at 1M rows each step copies ~2 x 32MB of bitmap; the
# sorted set moves ~32KB) — the quota-proportional win is ~9x here and
# grows with N
DEDUP_N = 1 << 20
DEDUP_QUOTA = 256
DEDUP_BATCH = 32
DEDUP_DEGREE = 16
DEDUP_DIM = 16
# matmul-form wave-scoring scenario (the PR-5 backend rewrite): 1M-row
# corpus at a serving-realistic embedding width, waves of 512 candidate
# lanes (a stage-1 fanout / small rerank block)
MM_N = 1 << 20
MM_DIM = 256
MM_WAVE = 512
MM_BATCHES = (1, 8, 32, 128)


def _time(fn, *args, reps=7):
    """Best-of-reps wall time (robust on shared/noisy CPU hosts)."""
    fn(*args)  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _scenario(name, setup, em, queries, true_ids, *, quota, expand_width,
              max_steps):
    """Old-vs-new sweep over batch sizes for one (metric, quota) regime."""
    entries = jnp.array([setup.index_d.medoid], jnp.int32)

    def old_one(q):  # the retired per-query path, frozen verbatim
        r = _legacy_beam.greedy_search(
            lambda ids: em.dists(q, ids), setup.index_d.adjacency,
            entries, n_points=setup.n, beam_width=BEAM, pool_size=BEAM,
            quota=quota, max_steps=max_steps)
        return r.pool_ids[:K], r.n_calls

    def new_search(qs):  # one shared batched hot loop
        b = qs.shape[0]
        r = batched_greedy_search(
            em.dists_batch, setup.index_d.adjacency, qs,
            jnp.broadcast_to(entries, (b, 1)), n_points=setup.n,
            beam_width=BEAM, pool_size=BEAM, quota=quota,
            expand_width=expand_width, max_steps=max_steps)
        return r.pool_ids[:, :K], r.n_calls

    old_one_j = jax.jit(old_one)
    old_vmap_j = jax.jit(jax.vmap(old_one))
    new_j = jax.jit(new_search)

    def old_perquery(qs):  # the retired serving loop: one query at a time
        outs = [old_one_j(q) for q in qs]
        return jax.block_until_ready(outs)[-1]

    batches = {}
    for b in BATCH_SIZES:
        qs = queries[:b]
        wall_pq = _time(old_perquery, qs, reps=3)
        wall_vm = _time(old_vmap_j, qs)
        wall_new = _time(new_j, qs)
        ids_old, calls_old = old_vmap_j(qs)
        ids_new, calls_new = new_j(qs)
        rec_old = float(metrics.recall_at_k(ids_old, true_ids[:b]).mean())
        rec_new = float(metrics.recall_at_k(ids_new, true_ids[:b]).mean())
        speedup_pq = wall_pq / wall_new
        speedup_vm = wall_vm / wall_new
        batches[str(b)] = {
            "qps_old_perquery": b / wall_pq,
            "qps_old_vmap": b / wall_vm,
            "qps_new": b / wall_new,
            "speedup_vs_perquery": speedup_pq,
            "speedup_vs_vmap": speedup_vm,
            "recall_old": rec_old, "recall_new": rec_new,
            "us_per_query_old_perquery": wall_pq / b * 1e6,
            "us_per_query_old_vmap": wall_vm / b * 1e6,
            "us_per_query_new": wall_new / b * 1e6,
            "mean_calls_old": float(np.asarray(calls_old).mean()),
            "mean_calls_new": float(np.asarray(calls_new).mean()),
        }
        emit(f"perf/{name}_old_perquery_b{b}", wall_pq / b * 1e6,
             f"us_per_query;recall={rec_old:.3f}")
        emit(f"perf/{name}_old_vmap_b{b}", wall_vm / b * 1e6,
             f"us_per_query;recall={rec_old:.3f}")
        emit(f"perf/{name}_new_b{b}", wall_new / b * 1e6,
             f"us_per_query;recall={rec_new:.3f}")
        emit(f"perf/{name}_speedup_b{b}", speedup_pq,
             f"x_vs_perquery;x_vs_vmap={speedup_vm:.2f};E={expand_width}")
    return {"expand_width": expand_width, "quota": quota, "batches": batches}


def _sharded_scenario(setup, em, queries) -> dict:
    """Device-parallel engine over the devices this process sees.

    Runs in this process (a chip belongs to one process), at every shard
    count of ``SHARD_COUNTS`` the device count allows, parity-checked
    bit-exact against the single-device engine. CI gives it 8 forced host
    devices through ``XLA_FLAGS`` (``scripts/ci.sh --bench``).
    """
    b = SHARD_BATCH
    qs = queries[:b]
    entries = jnp.broadcast_to(
        jnp.array([setup.index_d.medoid], jnp.int32), (b, 1))

    def search(shards):
        return lambda q: sharded_greedy_search(
            em.embeddings, setup.index_d.adjacency, q, entries,
            shards=shards, metric="l2", beam_width=BEAM, pool_size=BEAM,
            quota=QUOTA, expand_width=E_QUOTA, max_steps=4 * QUOTA)

    base_fn = search(1)
    base_wall = _time(base_fn, qs, reps=5)
    base = base_fn(qs)
    out = {"devices": len(jax.devices()),
           "unsharded_us_per_query": base_wall / b * 1e6, "shards": {}}
    for s in (s for s in SHARD_COUNTS if s <= out["devices"]):
        fn = search(s)
        wall = _time(fn, qs, reps=5)
        parity = all(np.array_equal(np.asarray(x), np.asarray(y))
                     for x, y in zip(base, fn(qs)))
        if not parity:
            raise AssertionError(f"sharded engine diverged at shards={s}")
        out["shards"][str(s)] = {
            "us_per_query": wall / b * 1e6,
            "speedup_vs_unsharded": base_wall / wall,
            "parity_bit_exact": parity,
        }
        emit(f"perf/sharded_s{s}_b{b}", wall / b * 1e6,
             f"us_per_query;x_vs_unsharded={base_wall / wall:.2f}"
             f";parity={parity}")
    return out


@functools.partial(jax.jit, static_argnames=(
    "n_points", "pool_size", "dedup", "set_capacity"))
def _dedup_init_j(entry_ids, quota, *, n_points, pool_size, dedup,
                  set_capacity):
    return beam.init_state(
        entry_ids, n_points=n_points, pool_size=pool_size, quota=quota,
        dedup=dedup, set_capacity=set_capacity)


@jax.jit
def _dedup_plan_j(state, adjacency, quota, beam_width, max_steps):
    return beam.plan_step(
        state, adjacency, beam_width=beam_width, quota=quota,
        max_steps=max_steps)


_dedup_commit_j = jax.jit(beam.commit_scores)
_dedup_active_j = jax.jit(lambda s, q, bw, ms: beam.active_mask(
    s, beam_width=bw, quota=q, max_steps=ms).any())


def _dedup_scenario() -> dict:
    """Sorted-set vs bitmap dedup state at quota 256 (both drive shapes)."""
    n, b, quota = DEDUP_N, DEDUP_BATCH, DEDUP_QUOTA
    rng = np.random.default_rng(0)
    adj = jnp.asarray(rng.integers(0, n, (n, DEDUP_DEGREE), dtype=np.int32))
    emb = jnp.asarray(rng.normal(size=(n, DEDUP_DIM)).astype(np.float32))
    qs = jnp.asarray(rng.normal(size=(b, DEDUP_DIM)).astype(np.float32))
    em = distances.EmbeddingMetric(emb)
    entries = jnp.zeros((b, 1), jnp.int32)
    seeds = jnp.asarray(rng.integers(0, n, (b, 8), dtype=np.int32))
    out = {"n": n, "quota": quota, "batch": b}

    # --- fused_loop: one while_loop program per backend (stage-1 shape) ---
    def fused(backend):
        return jax.jit(lambda q: batched_greedy_search(
            em.dists_batch, adj, q, entries, n_points=n, beam_width=BEAM,
            pool_size=BEAM, quota=quota, expand_width=E_QUOTA,
            max_steps=4 * quota, dedup=backend))

    f_bm, f_ss = fused("bitmap"), fused("sorted")
    wall = {"bitmap": _time(f_bm, qs, reps=5),
            "sorted": _time(f_ss, qs, reps=5)}
    r_bm, r_ss = f_bm(qs), f_ss(qs)
    parity = all(np.array_equal(np.asarray(x), np.asarray(y))
                 for x, y in zip(r_bm, r_ss))
    assert parity, "dedup backends diverged in the fused loop"
    out["fused_loop"] = {
        "us_per_query_bitmap": wall["bitmap"] / b * 1e6,
        "us_per_query_sorted": wall["sorted"] / b * 1e6,
        "speedup_sorted_vs_bitmap": wall["bitmap"] / wall["sorted"],
        "parity_bit_exact": parity,
    }
    emit("perf/dedup_fused_q256", wall["sorted"] / b * 1e6,
         f"us_per_query_sorted;x_vs_bitmap="
         f"{out['fused_loop']['speedup_sorted_vs_bitmap']:.2f}")

    # --- serve_drive: host-driven plan/commit dispatches (stage-2 shape) --
    qv = jnp.full((b,), quota, jnp.int32)
    bw = jnp.full((b,), BEAM, jnp.int32)
    ms = jnp.full((b,), 4 * quota, jnp.int32)

    def drive(backend):
        cap = quota if backend == "sorted" else None
        state, safe, keep = _dedup_init_j(
            seeds, qv, n_points=n, pool_size=BEAM, dedup=backend,
            set_capacity=cap)
        while True:
            dists = em.dists_batch(qs, safe)
            state = _dedup_commit_j(state, safe, keep, dists)
            if not bool(_dedup_active_j(state, qv, bw, ms)):
                break
            state, safe, keep, _ = _dedup_plan_j(state, adj, qv, bw, ms)
        return jax.block_until_ready(state)

    def best_of(fn, reps=5):
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    s_bm = drive("bitmap")  # compile
    s_ss = drive("sorted")
    dwall = {"bitmap": best_of(lambda: drive("bitmap")),
             "sorted": best_of(lambda: drive("sorted"))}
    dparity = (
        np.array_equal(np.asarray(s_bm.pool_ids), np.asarray(s_ss.pool_ids))
        and np.array_equal(np.asarray(s_bm.pool_dists),
                           np.asarray(s_ss.pool_dists))
        and np.array_equal(np.asarray(s_bm.n_calls),
                           np.asarray(s_ss.n_calls))
        and np.array_equal(np.asarray(s_bm.n_steps),
                           np.asarray(s_ss.n_steps))
        and np.array_equal(
            np.asarray(s_bm.scored),
            np.asarray(beam.scored_set_to_bitmap(s_ss.scored, n))))
    assert dparity, "dedup backends diverged in the serve drive"
    speedup = dwall["bitmap"] / dwall["sorted"]
    out["serve_drive"] = {
        "us_per_query_bitmap": dwall["bitmap"] / b * 1e6,
        "us_per_query_sorted": dwall["sorted"] / b * 1e6,
        "speedup_sorted_vs_bitmap": speedup,
        "parity_bit_exact": dparity,
    }
    # the gated headline: quota-proportional state on the serving stage-2
    # dispatch shape, where the (B, N) bitmap is copied every step
    out["speedup_at_quota_256"] = speedup
    emit("perf/dedup_serve_drive_q256", dwall["sorted"] / b * 1e6,
         f"us_per_query_sorted;x_vs_bitmap={speedup:.2f}")
    return out


def _matmul_scenario() -> dict:
    """MXU-form wave scoring (corpus-norm cache) vs gather-then-reduce.

    Two measurements per batch size, parity-asserted against each other
    (allclose distances AND identical per-wave top-10 ranking — recall@10
    unchanged):

    * ``score_stage`` — both forms score the **same resident wave** (rows
      gathered once, outside the timer): the gather-then-reduce inner
      reduction vs ``‖x‖² − 2·dot_general(rows, q) + ‖q‖²`` with ``‖x‖²``
      from the corpus-norm cache. This isolates exactly the computation
      the backend rewrite changes — the matmul form does ~⅓ fewer flops
      and its reduce is a BLAS/MXU ``dot_general``. The gated headline
      ``speedup_at_32`` comes from here (compute-bound, stable on a noisy
      host).
    * ``fused_op`` — the full ``ops.gather_score`` (ref vs xla_matmul
      backends) on random waves, recorded honestly: on this CPU host XLA
      fuses the row gather *into* the reduce loop (one pass, no (B, K, D)
      temp), while ``dot_general`` forces the gathered operand to
      materialize — so the full op is a memory-bandwidth wash here. The
      Pallas tile reads an XLA-gathered block too; on the chip neither
      form is timed yet, and the trajectory artifact records both.
    """
    rng = np.random.default_rng(7)
    corpus = jnp.asarray(
        rng.normal(size=(MM_N, MM_DIM)).astype(np.float32))
    view = ops.as_corpus_view(corpus)
    jax.block_until_ready(view.sq_norms)

    # the two scoring-stage forms, exactly as the backends lower them
    def score_reduce(qs, rows):
        return ((rows - qs[:, None]) ** 2).sum(-1)

    def score_matmul(qs, rows, sq):
        dots = jax.lax.dot_general(rows, qs, (((2,), (1,)), ((0,), (0,))))
        return jnp.maximum(sq - 2.0 * dots + (qs * qs).sum(-1)[:, None], 0.0)

    f_red = jax.jit(score_reduce)
    f_mm = jax.jit(score_matmul)
    f_op_ref = jax.jit(
        lambda q, i: ops.gather_score(corpus, q, i, backend="ref"))
    f_op_mm = jax.jit(
        lambda q, i: ops.gather_score(view, q, i, backend="xla_matmul"))

    def interleaved(fa, a_args, fb, b_args, reps=9):
        """Best-of with the two forms interleaved (shared host noise)."""
        wa = wb = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fa(*a_args))
            wa = min(wa, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fb(*b_args))
            wb = min(wb, time.perf_counter() - t0)
        return wa, wb

    out = {"n": MM_N, "dim": MM_DIM, "wave": MM_WAVE, "batches": {}}
    for b in MM_BATCHES:
        qs = jnp.asarray(rng.normal(size=(b, MM_DIM)).astype(np.float32))
        ids = jnp.asarray(
            rng.integers(0, MM_N, (b, MM_WAVE), dtype=np.int32))
        rows = jax.block_until_ready(corpus[ids])
        sq = jax.block_until_ready(view.sq_norms[ids])
        # parity: same distances (fp tolerance) and identical ranking
        d_red = np.asarray(f_red(qs, rows))
        d_mm = np.asarray(f_mm(qs, rows, sq))
        np.testing.assert_allclose(d_mm, d_red, rtol=2e-3, atol=5e-2)
        top_red = np.argsort(d_red, axis=1, kind="stable")[:, :K]
        top_mm = np.argsort(d_mm, axis=1, kind="stable")[:, :K]
        assert np.array_equal(top_red, top_mm), "matmul form changed recall"
        # the shipped op computes the same values as the bench form
        np.testing.assert_allclose(
            np.asarray(f_op_mm(qs, ids)), d_mm, rtol=1e-5, atol=1e-4)
        f_red(qs, rows).block_until_ready()
        f_mm(qs, rows, sq).block_until_ready()
        w_red, w_mm = interleaved(f_red, (qs, rows), f_mm, (qs, rows, sq))
        f_op_ref(qs, ids).block_until_ready()
        f_op_mm(qs, ids).block_until_ready()
        wo_ref, wo_mm = interleaved(f_op_ref, (qs, ids), f_op_mm, (qs, ids),
                                    reps=5)
        speed = w_red / w_mm
        out["batches"][str(b)] = {
            "score_stage_us_reduce": w_red / b * 1e6,
            "score_stage_us_matmul": w_mm / b * 1e6,
            "score_stage_speedup": speed,
            "fused_op_us_ref": wo_ref / b * 1e6,
            "fused_op_us_matmul": wo_mm / b * 1e6,
            "fused_op_speedup": wo_ref / wo_mm,
            "ranking_parity": True,
        }
        emit(f"perf/matmul_score_b{b}", w_mm / b * 1e6,
             f"us_per_query;x_vs_reduce={speed:.2f}"
             f";fused_op_x={wo_ref / wo_mm:.2f}")
    # gated headline: the scoring-stage rewrite at the serving batch size
    out["speedup_at_32"] = out["batches"]["32"]["score_stage_speedup"]
    out["fused_op_speedup_at_32"] = out["batches"]["32"]["fused_op_speedup"]
    return out


def _quantized_scenario() -> dict:
    """Quantized corpus residency (int8 / fp8 rows) vs f32, same 1M corpus.

    Three numbers per batch size, for each quantized mode the host's jax
    build supports:

    * ``recall_at_10`` — the quantized scoring path's wave top-10 against
      the exact-f32 ranking of the *same* wave. Both paths score the
      identical MM_WAVE-candidate set, so the comparison is at matched
      quota by construction; the quantization error is the only difference.
      The int8 mean across batch sizes is the gated headline
      (``result.quantized.recall_at_10``).
    * ``score_stage_speedup`` — interleaved best-of timing of the full
      fused ``ops.gather_score`` (xla_matmul backend) over the f32 view vs
      the quantized view. On this CPU host the dequant epilogue is extra
      ALU work against the same gather traffic, so this hovers near 1x —
      recorded honestly; the bytes-per-row column is where the win is (4x
      less residency = 4x more corpus per device on the accelerator lane).
    * ``bytes_per_row`` — full per-row residency from the view itself
      (codes + norm cache + scale/zero-point). ``compression_int8`` (the
      second gated headline) is the *row-payload* ratio — f32 code bytes
      over quantized code bytes, 4.0x for int8 — because the 8-byte norm
      cache rides both residencies identically and is not part of the
      compression lever; the full-residency ratio is also recorded
      (``residency_compression``) for honesty.
    """
    rng = np.random.default_rng(11)
    corpus = jnp.asarray(
        rng.normal(size=(MM_N, MM_DIM)).astype(np.float32))
    view_f32 = ops.as_corpus_view(corpus)
    views = {"int8": ops.as_corpus_view(corpus, quantize="int8")}
    try:
        views["fp8"] = ops.as_corpus_view(corpus, quantize="fp8")
    except ValueError:  # jax build without float8_e4m3fn
        pass
    jax.block_until_ready(view_f32.sq_norms)

    def fused(v):
        return jax.jit(
            lambda q, i, v=v: ops.gather_score(v, q, i, backend="xla_matmul"))

    f_f32 = fused(view_f32)
    f_q = {m: fused(v) for m, v in views.items()}

    def interleaved(fa, a_args, fb, b_args, reps=7):
        wa = wb = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fa(*a_args))
            wa = min(wa, time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(fb(*b_args))
            wb = min(wb, time.perf_counter() - t0)
        return wa, wb

    row_bytes_f32 = MM_DIM * 4
    out = {
        "n": MM_N, "dim": MM_DIM, "wave": MM_WAVE,
        "modes": sorted(views),
        "bytes_per_row": {"f32": view_f32.bytes_per_row,
                          **{m: v.bytes_per_row for m, v in views.items()}},
        "row_payload_compression": {
            m: row_bytes_f32 / (MM_DIM * v.rows.dtype.itemsize)
            for m, v in views.items()},
        "residency_compression": {
            m: view_f32.bytes_per_row / v.bytes_per_row
            for m, v in views.items()},
        "batches": {},
    }
    recalls = {m: [] for m in views}
    for b in MM_BATCHES:
        qs = jnp.asarray(rng.normal(size=(b, MM_DIM)).astype(np.float32))
        ids = jnp.asarray(
            rng.integers(0, MM_N, (b, MM_WAVE), dtype=np.int32))
        d_exact = np.asarray(f_f32(qs, ids))
        top_exact = np.argsort(d_exact, axis=1, kind="stable")[:, :K]
        rec = {}
        for m in views:
            d_q = np.asarray(f_q[m](qs, ids))
            top_q = np.argsort(d_q, axis=1, kind="stable")[:, :K]
            overlap = np.mean([
                len(set(top_q[r]) & set(top_exact[r])) / K
                for r in range(b)])
            rec[m] = float(overlap)
            recalls[m].append(float(overlap))
        f_f32(qs, ids).block_until_ready()
        f_q["int8"](qs, ids).block_until_ready()
        w_f32, w_i8 = interleaved(f_f32, (qs, ids), f_q["int8"], (qs, ids))
        out["batches"][str(b)] = {
            "score_stage_us_f32": w_f32 / b * 1e6,
            "score_stage_us_int8": w_i8 / b * 1e6,
            "score_stage_speedup": w_f32 / w_i8,
            "recall_at_10": rec,
        }
        emit(f"perf/quantized_score_b{b}", w_i8 / b * 1e6,
             f"us_per_query;x_vs_f32={w_f32 / w_i8:.2f}"
             f";recall@10_int8={rec['int8']:.4f}")
    # gated headlines: int8 fidelity at matched quota, and the residency win
    out["recall_at_10"] = float(np.mean(recalls["int8"]))
    out["recall_at_10_by_mode"] = {
        m: float(np.mean(v)) for m, v in recalls.items()}
    out["compression_int8"] = out["row_payload_compression"]["int8"]
    out["speedup_at_32"] = out["batches"]["32"]["score_stage_speedup"]
    return out


def run() -> dict:
    setup = Setup(n=4096, n_queries=max(BATCH_SIZES))
    em_d = distances.EmbeddingMetric(setup.data.corpus_d)
    em_D = distances.EmbeddingMetric(setup.data.corpus_D)
    true_d, _ = em_d.brute_force(setup.data.queries_d, K)
    true_D, _ = em_D.brute_force(setup.data.queries_D, K)

    stage2 = _scenario(
        "stage2_quota", setup, em_D, setup.data.queries_D, true_D,
        quota=QUOTA, expand_width=E_QUOTA, max_steps=4 * QUOTA)
    stage1 = _scenario(
        "stage1_unbounded", setup, em_d, setup.data.queries_d, true_d,
        quota=_legacy_beam.NO_QUOTA, expand_width=E_UNBOUNDED, max_steps=128)
    sharded = _sharded_scenario(setup, em_D, setup.data.queries_D)
    dedup = _dedup_scenario()
    matmul = _matmul_scenario()
    quantized = _quantized_scenario()

    # kernel micro-benches (XLA path = production CPU path; pallas path is
    # interpret-mode, correctness-only on CPU)
    corpus = setup.data.corpus_d
    qs = setup.data.queries_d[:32]
    idsb = jax.random.randint(jax.random.PRNGKey(0), (32, 24), 0, setup.n)
    f_x = jax.jit(lambda c, q, i: ops.gather_score(c, q, i))
    emit("perf/gather_score_xla", _time(f_x, corpus, qs, idsb) * 1e6 / 32,
         "us_per_query_row")
    bi = jax.random.randint(jax.random.PRNGKey(1), (32, 32), 0, setup.n)
    bd = jax.random.uniform(jax.random.PRNGKey(2), (32, 32))
    cd = jax.random.uniform(jax.random.PRNGKey(3), (32, 24))
    f_m = jax.jit(lambda a, b_, c, d: ops.beam_merge_topk(a, b_, c, d))
    emit("perf/beam_merge_xla", _time(f_m, bi, bd, idsb, cd) * 1e6 / 32,
         "us_per_query_row")

    payload = {
        "beam_width": BEAM,
        "n": setup.n,
        "stage2_quota": stage2,
        "stage1_unbounded": stage1,
        "sharded": sharded,
        "dedup": dedup,
        "matmul": matmul,
        "quantized": quantized,
        # headline: batched engine vs the retired per-query serving loop,
        # on the paper's quota-bounded cost model, at batch 32
        "speedup_at_32": stage2["batches"]["32"]["speedup_vs_perquery"],
        "speedup_at_32_vs_vmap": stage2["batches"]["32"]["speedup_vs_vmap"],
    }
    return payload


if __name__ == "__main__":
    from benchmarks.common import drain_emitted

    drain_emitted()
    _t0 = time.time()
    _result = run()
    write_bench_json("search_perf", {  # same schema as benchmarks/run.py
        "bench": "perf",
        "wall_seconds": time.time() - _t0,
        "rows": drain_emitted(),
        "result": _result,
    })
