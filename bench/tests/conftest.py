"""Shared set-up of the benchmark's own tests (run on the CPU):

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

# a cell's shapes cut down until one run takes seconds on a CPU
TINY_EXPENSIVE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                      head_dim=16, d_ff=128, vocab=512, embed_dim=32)
TINY_CHEAP = dict(n_layers=1, d_model=32, n_heads=2, n_kv_heads=2,
                  head_dim=16, d_ff=64, vocab=512, embed_dim=16)
# limits of the tiny cells: the bfloat16 expensive tower against the
# float32 reference reads about 0.004 at these widths, its fp8 control
# about 0.05; the float32 cheap tower on the CPU reads under 1e-6, its
# bfloat16 control about 0.005
TINY_LIMITS = {"dist_gap": 0.02, "nn_miss": 0.5, "cheap_gap": 0.001}
# the traffic at a test size: more distinct queries than a fast CPU serves
# in a tiny cell's ramp and window
TINY_MIX = {"quota": 16, "k": 5, "warmup_requests": 5, "check_sample": 8,
            "batch": 4, "max_requests": 2000, "ramp_s": 1.0}


@pytest.fixture(scope="session")
def spec():
    from harness.spec import Spec

    return Spec(ROOT)


@pytest.fixture
def tiny(spec):
    """``tiny(cell) -> (cfg, mix)``: the cell's files at a test size."""

    def make(cell: str):
        w = spec.cell(cell)
        cfg = spec.load_config(w["config"])
        mix = spec.load_traffic(w["traffic"])
        cfg.update(TINY_EXPENSIVE, n_docs=2048,
                   doc_len=min(cfg["doc_len"], 16),
                   query_len=min(cfg["doc_len"], 16), limits=TINY_LIMITS)
        cfg["cheap_tower"] = {**cfg["cheap_tower"], **TINY_CHEAP}
        cfg["engine"] = {**cfg["engine"], "slots": 4}
        return cfg, {**mix, **TINY_MIX}

    return make
