"""The slot drive's spans and work counters, read from a profiler trace.

A small async engine serves more requests than it has slots under
``jax.profiler`` on the CPU. The trace must hold every ``serve.*`` span
the engine opens, with its ids, nested as the engine nests them: the
admission's parts inside ``serve.admit``, a wave's parts inside
``serve.wave``, all of them on the drive thread and the tower lane's spans
on a thread of their own. The engine's own span counters must count what
the trace holds, and its work counters what the requests were charged.
"""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import qwen3_0_6b
from repro.models import transformer as T
from repro.serve import BiMetricEngine, EmbedTower, SearchRequest

DRIVE = {  # span -> (ids, the span it opens inside; None = top level)
    "serve.admit": (("group", "requests"), None),
    "serve.cheap_embed": (("group",), "serve.admit"),
    "serve.stage1": (("group",), "serve.admit"),
    "serve.query_wait": (("group",), "serve.admit"),
    "serve.wave": (("wave", "entry"), None),
    "serve.plan": (("wave",), "serve.wave"),
    "serve.drain_wait": (("wave",), "serve.wave"),
    "serve.gather": (("wave",), "serve.wave"),
    "serve.commit": (("wave",), "serve.wave"),
    "serve.resolve": (("resolved",), None),
}
TOWER = {
    "serve.tower.drain": ("wave", "rows"),
    "serve.tower.query_embed": ("group",),
}


@pytest.fixture(scope="module")
def served(tmp_path_factory):
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
    eng = BiMetricEngine(cheap, expensive, corpus, slots=2)
    reqs = [SearchRequest(tokens=corpus[i], quota=q, k=5)
            for i, q in zip((3, 40, 77, 11, 58, 90), (15, 4, 9, 12, 6, 10))]
    futs = []
    pop = eng._pop_group

    def pop_then_send_the_rest(n):
        # the rest arrive just after the drive loop found the queue empty
        # with the first request resident: the next wave's drain stages them
        group = pop(n)
        if not group and len(futs) == 1 and eng._pool.occupied.any():
            futs.extend(eng.submit(r) for r in reqs[1:])
        return group

    eng._pop_group = pop_then_send_the_rest
    c0 = eng.counters()
    log_dir = tmp_path_factory.mktemp("serve_trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        futs.append(eng.submit(reqs[0]))
        results = [futs[0].result(timeout=300)]
        assert len(futs) == len(reqs)
        results += [f.result(timeout=300) for f in futs[1:]]
        eng.close()
    finally:
        jax.profiler.stop_trace()
    c1 = eng.counters()
    path = sorted(glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                            recursive=True))[-1]
    lines = []  # one per host thread: [(name, start, end, stats)]
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                   for e in line.events if e.name.startswith("serve.")]
            if evs:
                lines.append(evs)
    return results, c0, c1, lines


def _by_name(lines):
    out: dict = {}
    for line in lines:
        for ev in line:
            out.setdefault(ev[0], []).append(ev)
    return out


def test_every_span_appears_with_its_ids_on_its_thread(served):
    _, _, _, lines = served
    drive = [ln for ln in lines if any(e[0] == "serve.wave" for e in ln)]
    tower = [ln for ln in lines if any(e[0] == "serve.tower.drain"
                                       for e in ln)]
    assert len(drive) == 1 and len(tower) == 1 and drive != tower
    assert {e[0] for e in drive[0]} == set(DRIVE)
    assert {e[0] for e in tower[0]} == set(TOWER)
    assert len(lines) == 2  # no serve.* span on any other thread
    for name, start, end, stats in drive[0] + tower[0]:
        ids = DRIVE[name][0] if name in DRIVE else TOWER[name]
        assert set(ids) <= set(stats), (name, stats)
        assert end >= start


def test_spans_nest_as_the_engine_opens_them(served):
    _, _, _, lines = served
    drive = next(ln for ln in lines if any(e[0] == "serve.wave" for e in ln))
    admits_in_waves = 0
    for name, start, end, stats in drive:
        parent = DRIVE[name][1]
        around = [e for e in drive if e[0] != name
                  and e[1] <= start and end <= e[2]]
        if parent is None and name != "serve.admit":
            assert not around, (name, around)
            continue
        if name == "serve.admit":
            # staged on its own, or inside a wave while its drain runs
            assert all(e[0] == "serve.wave" for e in around), around
            admits_in_waves += bool(around)
            continue
        outer = [e for e in around if e[0] == parent]
        assert len(outer) == 1, (name, around)
        key = DRIVE[name][0][0]
        assert outer[0][3][key] == stats[key]  # the parent's id
    assert admits_in_waves > 0  # the next group staged during a drain
    by = _by_name(lines)
    waves = {e[3]["wave"] for e in by["serve.wave"]}
    groups = {e[3]["group"] for e in by["serve.admit"]}
    assert {e[3]["wave"] for e in by["serve.tower.drain"]} <= waves
    assert {e[3]["group"] for e in by["serve.tower.query_embed"]} == groups
    assert sum(e[3]["entry"] for e in by["serve.wave"]) == len(groups)


def test_span_counters_count_the_trace(served):
    _, c0, c1, lines = served
    by = _by_name(lines)
    for name in (*DRIVE, *TOWER):
        assert c1.span_n.get(name, 0) - c0.span_n.get(name, 0) \
            == len(by[name]), name
        assert c1.span_s[name] > c0.span_s.get(name, 0.0)


def test_work_counters_match_the_requests(served):
    results, c0, c1, lines = served
    by = _by_name(lines)
    assert c1.waves - c0.waves == len(by["serve.wave"])
    assert c1.waves - c0.waves >= len(by["serve.admit"])
    assert c1.admitted - c0.admitted == len(results)
    assert c1.doc_lookups - c0.doc_lookups \
        == sum(r.stats.D_calls for r in results)
    assert sum(e[3]["rows"] for e in by["serve.tower.drain"]) \
        == c1.doc_lookups - c0.doc_lookups
    drained = c1.drained_rows - c0.drained_rows
    assert 0 < drained <= c1.doc_lookups - c0.doc_lookups
    batches = c1.drain_batches - c0.drain_batches
    assert 0 < batches <= len(by["serve.tower.drain"])
    # every tower call pads to its batch of 64 rows; a group's query embeds
    # are one such call per tower, holding a token in each request's row
    groups = len(by["serve.admit"])
    assert c1.expensive_rows - c0.expensive_rows == 64 * (batches + groups)
    assert c1.expensive_rows_useful - c0.expensive_rows_useful \
        == drained + len(results)
    assert c1.cheap_rows - c0.cheap_rows == 64 * groups
    assert c1.cheap_rows_useful - c0.cheap_rows_useful == len(results)
