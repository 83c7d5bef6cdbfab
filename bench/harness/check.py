"""Whether what the timed path returned is correct.

Numbers compared, each against its limit (a run is correct when every
number is at or under its limit):

* ``dist_gap``: over a sample of the window's finished requests, drawn
  from the seed, the widest gap between a distance the engine returned and
  ``‖E(doc) − E(query)‖`` recomputed by the configuration's plain reference
  in float32. Embeddings are unit vectors, so the gap is on the scale of
  distances in [0, 2].
* ``cheap_gap``: over the same sampled requests, the widest distance
  between the cheap tower's embedding of the query as the program computed
  it during the run (recorded at ``EmbedTower.embed``) and the reference's
  in float32. It holds the cheap tower to the accuracy the configuration
  states (its ``assumed.cheap_tower_precision``), which ``dist_gap`` (the
  expensive tower's distances) cannot see.
* ``nn_miss``: the share of sampled requests whose first result is not the
  reference's nearest among the returned ids and the query's planted
  neighbour. It reads the search as a whole: the cheap tower and the index
  (which must lead stage 1 to the neighbour), and stage 2 (which must rank
  it first under D). It is compared only in a configuration whose file
  gives it a limit: where the control does not separate it from sound
  runs, it is printed as a reading.

Limits live in the configuration's file (``limits``), set from the
program's readings and the control's (``bench/control.py``).
* ``unanswered``: requests of the window that failed or never resolved.
* ``over_quota``: finished requests that spent more expensive-tower
  document scorings than their quota (the paper's exact budget).
* ``bad_rows``: finished requests whose answer is not k distinct ids of
  the corpus in ascending distance, or resolved degraded.

The last three are exact: their limit is 0.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from harness import spec

EXACT = ("unanswered", "over_quota", "bad_rows")


def load_reference(cfg: dict, bench_dir: Path):
    """The configuration's plain reference, ``embed(params, tokens, t,
    precision=...)``; ``t`` is a tower's sizes, its module's name among
    them (``harness.model.tower_dict``)."""
    return spec.config_module(bench_dir, cfg["reference"])


def exact_counts(done: list, n_failed: int, *, quota: int, k: int,
                 n_docs: int) -> dict:
    """The exact checks over every finished request of the window."""
    over = bad = 0
    for r in done:
        over += int(r.stats.D_calls > quota)
        ids, dd = np.asarray(r.ids), np.asarray(r.dists)
        ok = (len(ids) == k and len(np.unique(ids)) == k
              and ids.min() >= 0 and ids.max() < n_docs
              and bool(np.all(np.isfinite(dd)))
              and bool(np.all(np.diff(dd) >= 0)) and not r.stats.degraded)
        bad += int(not ok)
    return {"unanswered": n_failed, "over_quota": over, "bad_rows": bad}


def sample(n_done: int, size: int, seed: int) -> np.ndarray:
    """Indices of the finished requests the comparison reads."""
    rng = np.random.default_rng([seed, 3])
    return np.sort(rng.choice(n_done, min(size, n_done), replace=False))


def reference_gaps(ref, params: dict, t: dict, corpus: np.ndarray,
                   queries: np.ndarray, planted: np.ndarray, answers: list,
                   precisions=("float32",)) -> dict:
    """``dist_gap`` and ``nn_miss`` of ``answers`` (ids, dists) against the
    reference; with ``"fp8"`` in ``precisions`` also the control's
    ``dist_gap`` over the same pairs (``control_dist_gap``) and its
    ``nn_miss``: how often the control's nearest candidate is not the
    reference's (``control_nn_miss``)."""
    docs = np.unique(np.concatenate(
        [np.asarray(ids) for ids, _ in answers] + [planted]))
    col = {int(d): i for i, d in enumerate(docs)}
    emb = {}
    for p in precisions:
        e_docs = ref.embed(params, corpus[docs], t, precision=p)
        e_q = ref.embed(params, queries, t, precision=p)
        emb[p] = (e_docs, e_q)
    e_docs, e_q = emb["float32"]
    gap = 0.0
    miss = ctl_miss = 0
    ctl = 0.0
    for i, (ids, dists) in enumerate(answers):
        ids = np.asarray(ids, np.int64)
        rows = [col[int(x)] for x in ids]
        d_ref = np.linalg.norm(e_docs[rows] - e_q[i], axis=1)
        if len(ids):
            gap = max(gap, float(np.max(np.abs(np.asarray(dists) - d_ref))))
        cand = np.unique(np.append(ids, planted[i]))
        d_cand = np.linalg.norm(
            e_docs[[col[int(x)] for x in cand]] - e_q[i], axis=1)
        best = int(cand[np.argmin(d_cand)])
        miss += int(not len(ids) or int(ids[0]) != best)
        if "fp8" in precisions and len(ids):
            c_docs, c_q = emb["fp8"]
            d_ctl = np.linalg.norm(c_docs[rows] - c_q[i], axis=1)
            ctl = max(ctl, float(np.max(np.abs(d_ctl - d_ref))))
            c_cand = np.linalg.norm(
                c_docs[[col[int(x)] for x in cand]] - c_q[i], axis=1)
            ctl_miss += int(int(cand[np.argmin(c_cand)]) != best)
    n = max(1, len(answers))
    out = {"dist_gap": gap, "nn_miss": miss / n}
    if "fp8" in precisions:
        out["control_dist_gap"] = ctl
        out["control_nn_miss"] = ctl_miss / n
    return out


def cheap_gaps(ref, params: dict, t: dict, queries: np.ndarray,
               outputs: dict, controls=()) -> dict:
    """``cheap_gap`` of the program's cheap query embeddings ``outputs``
    (token bytes -> embedding) against the reference; for each precision
    in ``controls`` also the control's, ``control_cheap_gap_<precision>``.
    A query the program never embedded reads 2, the widest distance two
    unit vectors can have."""
    if not len(queries):
        return {"cheap_gap": 0.0}
    e_ref = ref.embed(params, queries, t)
    dim = e_ref.shape[1]
    prog = np.stack([outputs.get(q.tobytes(), np.full(dim, np.nan))
                     for q in queries]).astype(np.float64)
    gaps = np.linalg.norm(prog - e_ref, axis=1)
    out = {"cheap_gap": float(np.max(np.nan_to_num(gaps, nan=2.0)))}
    for p in controls:
        e = ref.embed(params, queries, t, precision=p)
        out[f"control_cheap_gap_{p}"] = float(
            np.max(np.linalg.norm(e - e_ref, axis=1)))
    return out


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for every number compared:
    the exact ones and those the configuration gives a limit."""
    lim = {**{k: 0 for k in EXACT}, **limits}
    checks = {k: {"value": numbers[k], "limit": lim[k]} for k in lim}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
