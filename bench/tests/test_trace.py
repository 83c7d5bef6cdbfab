"""The trace reduction on a small trace recorded on a TPU v5 lite.

``data/fixture.xplane.pb`` holds, inside a ``bench.window`` span, three
rounds of: a 1024×1024 bf16 matmul program inside an ``expensive.embed``
span, a 2 ms sleep inside ``bench.wait``, and an elementwise program
inside ``cheap.embed``.
"""
from __future__ import annotations

import pytest
from conftest import BENCH

from harness import trace

FIXTURE = BENCH / "tests" / "data" / "fixture.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(str(FIXTURE))


def test_window_and_busy_time(reduced):
    assert reduced.n_chips == 1
    assert reduced.window_s == pytest.approx(0.01309, abs=1e-4)
    assert 0 < reduced.busy_s < reduced.window_s
    # the union of operation intervals never exceeds the programs' time
    assert reduced.busy_s <= sum(reduced.module_s.values()) + 1e-9
    assert len(reduced.module_s) == 2


def test_idle_gaps_are_named_by_the_span_around_them(reduced):
    gaps = reduced.idle_gaps
    assert len(gaps) == 10
    assert [s for _, s in gaps] == sorted((s for _, s in gaps), reverse=True)
    # the three 2 ms sleeps fall in gaps the client's wait span names
    waited = sum(s for n, s in gaps if n == "bench.wait")
    assert waited >= 3 * 0.002 * 0.9
    assert {n for n, _ in gaps} <= {"bench.wait", "expensive.embed",
                                    "cheap.embed"}


def test_breakdown_shape(reduced):
    b = trace.breakdown(reduced)
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] == max(reduced.module_s.values())


def test_no_window_span_is_an_error(tmp_path):
    # a trace without the benchmark's window span cannot be reduced
    import jax.numpy as jnp

    with trace.capture(str(tmp_path)):
        jnp.ones(4).block_until_ready()
    path = trace.find_xplane(str(tmp_path))
    with pytest.raises(ValueError):
        trace.reduce(path)
