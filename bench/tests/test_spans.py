"""Interval arithmetic (``harness.spans``), the engine's spans in a trace
(``harness.serve_trace``) and the readers built on them.

The readers of ``admit_idle_share``, ``wave_idle_share`` and ``stage1_ms``
read ``data/serve_fixture.xplane.pb``, recorded on a TPU v5 lite by
``record_serve_fixture.py``: three rounds of the engine's span pattern in
which ``serve.stage1`` holds a 4 ms host sleep and ``serve.gather`` a 2 ms
one with the device idle, while the ``serve.admit`` staged inside each
wave sleeps 3 ms with the device running the drain's matmuls.
"""
from __future__ import annotations

import importlib.util
import json
import os
import random
import shutil

import pytest
from conftest import BENCH

from harness import serve_trace, spans, trace

DATA = BENCH / "tests" / "data"


@pytest.mark.parametrize("a, b, union, inter, minus", [
    ([], [(0, 5)], [], [], []),
    ([(0, 5)], [], [(0, 5)], [], [(0, 5)]),
    ([(3, 6), (0, 4)], [(5, 9)], [(0, 6)], [(5, 6)], [(0, 5)]),
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 10)], [(2, 3), (5, 7)],
     [(0, 2), (3, 5), (7, 10)]),
    ([(0, 2), (2, 4)], [(1, 3)], [(0, 4)], [(1, 3)], [(0, 1), (3, 4)]),
    ([(0, 4), (6, 8)], [(4, 6)], [(0, 4), (6, 8)], [], [(0, 4), (6, 8)]),
    ([(1, 1), (2, 5)], [(0, 9)], [(2, 5)], [(2, 5)], []),
])
def test_interval_arithmetic(a, b, union, inter, minus):
    assert spans.union(a) == union
    assert spans.intersect(a, b) == inter
    assert spans.subtract(a, b) == minus
    assert spans.total_s(a) == pytest.approx(
        sum(e - s for s, e in union) * 1e-9)


def test_interval_arithmetic_against_counting_points():
    rng = random.Random(7)

    def cover(ivs):
        return {t for s, e in ivs for t in range(s, e)}

    for _ in range(500):
        a, b = ([(s, s + rng.randint(0, 20)) for s in
                 (rng.randint(0, 90) for _ in range(rng.randint(0, 6)))]
                for _ in range(2))
        for got, want in ((spans.union(a), cover(a)),
                          (spans.intersect(a, b), cover(a) & cover(b)),
                          (spans.subtract(a, b), cover(a) - cover(b))):
            assert cover(got) == want
            assert all(s < e for s, e in got)
            assert all(x[1] < y[0] for x, y in zip(got, got[1:]))


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "_t_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


READERS = ["admit_idle_share", "wave_idle_share", "stage1_ms"]


@pytest.fixture(scope="module")
def st():
    return serve_trace.reduce(DATA / "serve_fixture.xplane.pb")


@pytest.fixture
def traced(tmp_path, monkeypatch):
    """``traced(fixture) -> ctx``: a traced run's ``ctx`` whose trace
    directory holds ``fixture`` (the newest trace) and an older one."""
    monkeypatch.setattr(serve_trace, "TRACE_ROOT", tmp_path)

    def make(name):
        older = tmp_path / "other" / "older.xplane.pb"
        newest = tmp_path / "cell" / "run" / "t.xplane.pb"
        for src, dst in (("fixture.xplane.pb", older), (name, newest)):
            dst.parent.mkdir(parents=True, exist_ok=True)
            shutil.copy(DATA / src, dst)
        os.utime(older, (1, 1))
        return {"trace": trace.reduce(str(newest))}

    return make


def test_fixture_spans_sit_on_two_threads(st):
    drive = {ln for name, evs in st.spans.items()
             if not name.startswith("serve.tower.") for *_, ln in evs}
    tower = {ln for name, evs in st.spans.items()
             if name.startswith("serve.tower.") for *_, ln in evs}
    assert len(drive) == 1 and len(tower) == 1 and drive != tower
    assert len(st.spans["serve.admit"]) == 6
    assert len(st.spans["serve.wave"]) == 3
    # idle intervals lie in the window, apart from every busy interval
    r = trace.reduce(str(DATA / "serve_fixture.xplane.pb"))
    assert st.window_s == r.window_s
    assert spans.total_s(st.idle) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    text = "\n".join(serve_trace.describe(st))
    assert "span serve.stage1: 3 in the window" in text
    assert "serve.admit > serve.stage1" in text


def test_for_ctx_reads_the_trace_the_run_reduced(traced):
    ctx = traced("serve_fixture.xplane.pb")
    got = serve_trace.for_ctx(ctx)
    assert got is not None and got.window_s == ctx["trace"].window_s
    assert len(got.spans["serve.stage1"]) == 3
    # a trace whose window another run reduced is not read
    ctx["trace"].window_s += 1e-9
    assert serve_trace.for_ctx(ctx) is None


def test_admit_idle_share_counts_only_idle_admissions(st, traced):
    ctx = traced("serve_fixture.xplane.pb")
    value = _reader("admit_idle_share")(ctx)
    idle_s = value / 100 * st.window_s
    # three stage-1 sleeps of 4 ms with the device idle ...
    assert idle_s >= 3 * 0.004 * 0.9
    # ... and nothing of the admissions staged while the drains ran
    inside = spans.intersect(st.inside("serve.admit"),
                             st.inside("serve.wave"))
    assert spans.total_s(inside) >= 3 * 0.003 * 0.9
    assert spans.total_s(spans.intersect(st.idle, inside)) \
        < 0.1 * spans.total_s(inside)
    assert idle_s <= spans.total_s(
        spans.subtract(st.inside("serve.admit"), inside)) + 1e-9


def test_wave_idle_share_leaves_admissions_out(st, traced):
    value = _reader("wave_idle_share")(traced("serve_fixture.xplane.pb"))
    drive = spans.subtract(st.inside("serve.wave"), st.inside("serve.admit"))
    # the three 2 ms gathers, the device idle, over the waves' own time
    assert 100 * 3 * 0.002 * 0.9 / spans.total_s(drive) <= value < 100


def test_stage1_ms_reads_the_spans_as_the_counters_do(st, traced):
    value = _reader("stage1_ms")(traced("serve_fixture.xplane.pb"))
    durations = [e - s for s, e in st.closed("serve.stage1")]
    assert len(durations) == 3 and value >= 4.0
    assert value == pytest.approx(1e-6 * sum(durations) / 3)
    # the trace's clock and the engine's span counters agree on the length
    snaps = json.loads((DATA / "serve_fixture.counters.json").read_text())
    n, s = (snaps["close"][k]["serve.stage1"] - snaps["open"][k].get(
        "serve.stage1", 0) for k in ("span_n", "span_s"))
    assert n == 3
    assert value == pytest.approx(1000 * s / n, abs=0.2)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_spans_reads_nothing(name, traced):
    # a chip trace of a program that opens no serve.* span
    assert _reader(name)(traced("fixture.xplane.pb")) is None
    # an untraced run
    assert _reader(name)({}) is None
