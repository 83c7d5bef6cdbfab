"""The program's own counters against the harness's, at a test size.

A whole run of a cell on the CPU, through the harness's own path. Over the
same interval, from the engine's rest after the warm-up until it is closed,
the rows each tower's program computed, those that held a token and the
documents the expensive tower drained must read the same in
``EngineCounters`` as in the harness's ``RowCounts``, which counts them by
wrapping the towers' jitted programs.
"""
from __future__ import annotations

import time

from conftest import ROOT

from harness.cell import run_cell

SEED = 2**33 + 29


def test_engine_counters_equal_the_harness_row_counts(spec, tiny):
    cfg, mix = tiny("scidocs.batch")
    edges = {}

    def at_rest(eng):
        edges["eng"] = eng
        edges["before"] = (eng.counters(), eng.cheap.snapshot(),
                           eng.expensive.snapshot())

    out = run_cell(spec, "scidocs.batch", SEED, 2.0, False,
                   t_start=time.perf_counter(),
                   trace_dir=ROOT / ".bench_trace" / "test", cfg=cfg,
                   mix=mix, patch=at_rest)
    assert out["correct"], out["checks"]
    eng = edges["eng"]  # closed: its threads have joined
    e0, cheap0, exp0 = edges["before"]
    e1, cheap1, exp1 = (eng.counters(), eng.cheap.snapshot(),
                        eng.expensive.snapshot())
    assert e1.drained_rows - e0.drained_rows > 0
    assert (e1.expensive_rows - e0.expensive_rows
            == exp1.computed - exp0.computed)
    assert (e1.expensive_rows_useful - e0.expensive_rows_useful
            == exp1.useful - exp0.useful)
    assert e1.drained_rows - e0.drained_rows == exp1.doc_rows - exp0.doc_rows
    assert e1.cheap_rows - e0.cheap_rows == cheap1.computed - cheap0.computed
    assert (e1.cheap_rows_useful - e0.cheap_rows_useful
            == cheap1.useful - cheap0.useful)
