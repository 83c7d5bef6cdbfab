"""The metric readers and the row counts they read, on made-up runs."""
from __future__ import annotations

import importlib.util
import types

import numpy as np
import pytest
from conftest import BENCH

from harness import towers


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        "_t_" + name.replace(".", "_"), BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _rec(start, end, D_calls=0):
    stats = types.SimpleNamespace(compute_ms=(end - start) * 1e3,
                                  D_calls=D_calls)
    return types.SimpleNamespace(done=end, result=types.SimpleNamespace(
        stats=stats))


@pytest.mark.parametrize("services, expected", [
    # inside the window, straddling each edge, outside it
    ([(2, 6), (8, 12), (-2, 2), (10, 14), (-5, -1), (12, 20)], 3.0),
    # back-to-back services of 4 s read 3 per 12 s window, however the
    # window falls on them: the count does not move in steps of a service
    ([(-4, 0), (0, 4), (4, 8), (8, 12), (12, 16)], 3.0),
    ([(-3, 1), (1, 5), (5, 9), (9, 13), (13, 17)], 3.0),
])
def test_qps_counts_the_share_of_each_service_in_the_window(services,
                                                            expected):
    ctx = {"t0": 0.0, "t_end": 12.0, "window_s": 12.0,
           "records": [_rec(s, e) for s, e in services]}
    qps = _reader("qps").read(ctx)
    assert qps == pytest.approx(expected / 12.0)


def test_doc_cache_hit_share_covers_the_same_requests():
    rows = towers.RowCounts(doc_rows=30)
    ctx = {"records": [_rec(0, 1, D_calls=40), _rec(0, 2, D_calls=60),
                       types.SimpleNamespace(result=None)],
           "towers_all": {"expensive": rows}}
    assert _reader("doc_cache_hit_share").read(ctx) == pytest.approx(70.0)


def test_rows_are_counted_at_the_tower_program():
    """Padding, empty slots and corpus rows, whatever pads the call."""
    corpus = np.arange(1, 1 + 6 * 4, dtype=np.int32).reshape(6, 4)
    seen = []

    class Fake(towers.CountedTower):
        def __post_init__(self):
            self._embed = lambda p, toks: (seen.append(toks.shape[0])
                                           or np.ones((toks.shape[0], 2)))

    t = Fake(params={}, cfg=None).attach(
        "expensive", {r.tobytes() for r in corpus})
    queries = np.zeros((3, 4), np.int32)
    queries[0] = 7  # a query, two empty slots
    t.embed(queries, batch=4)
    t.embed(corpus[:5], batch=4)
    c = t.snapshot()
    assert seen == [4, 4, 4]
    assert (c.calls, c.asked, c.computed) == (2, 8, 12)
    assert c.useful == 1 + 5 and c.doc_rows == 5
    share = _reader("tower_pad_share").read({"towers": {"expensive": c}})
    assert share == pytest.approx(100.0 * 6 / 12)
