"""The correctness comparison separates a sound run from broken ones.

Each test drives a whole run of a cell, at a test size on the CPU, through
the harness's own path (set-up, the window, the check); only the look for
a chip is skipped. A sound run must come out correct; the controls (the
reference in the program's place, a precision below each tower's) must
read over the ``dist_gap`` and ``cheap_gap`` limits; and a run whose timed
path is broken underneath must come out not correct, once for each fault a
search cell can have.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import ROOT

from harness.cell import run_cell

CELLS = ["scidocs.batch"]
SEED = 2**33 + 17


def _run(spec, tiny, cell, **kw):
    cfg, mix = tiny(cell)
    return run_cell(spec, cell, SEED, 2.0, False, t_start=time.perf_counter(),
                    trace_dir=ROOT / ".bench_trace" / "test", cfg=cfg,
                    mix=mix, **kw)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_the_control_is_not(spec, tiny, cell):
    out = _run(spec, tiny, cell, control=True)
    gap = out["checks"]["dist_gap"]
    cheap = out["checks"]["cheap_gap"]
    ctl = out["control"]
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert gap["value"] <= gap["limit"] < ctl["control_dist_gap"]
    assert cheap["value"] <= cheap["limit"] < ctl["control_cheap_gap_bf16"]
    assert ctl["control_cheap_gap_bf16"] < ctl["control_cheap_gap_fp8"]
    assert list(out)[-1] == "checks"


def _alter_answers(eng):
    """Every document embedding the drains produce comes out shifted. The
    query embeddings stay as they are: the same shift on both sides would
    keep every distance."""
    embed = eng.expensive.embed
    docs = {row.tobytes() for row in eng.corpus_tokens}

    def altered(tokens, batch=64):
        out = embed(tokens, batch)
        is_doc = np.array([row.tobytes() in docs for row in tokens])
        out[is_doc] = np.roll(out[is_doc], 1, axis=1)
        return out

    eng.expensive.embed = altered


def _alter_cheap(eng):
    """The cheap tower's program puts out every query embedding shifted."""
    program = eng.cheap._embed
    eng.cheap._embed = lambda p, toks: jnp.roll(program(p, toks), 1, axis=1)


def _drop_half(eng):
    """Every second request is left out: its future never gets an answer."""
    submit = eng.submit
    n = [0]

    def dropping(req):
        fut = submit(req)
        n[0] += 1
        if n[0] % 2 == 0:
            fut.cancel()
        return fut

    eng.submit = dropping


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged", "cheap_altered"])
def test_broken_timed_path_is_not_correct(spec, tiny, cell, fault,
                                          monkeypatch):
    from repro.serve import engine as engine_mod

    def patch(eng):
        if fault == "answer_altered":
            _alter_answers(eng)
        elif fault == "half_left_out":
            _drop_half(eng)
        elif fault == "cheap_altered":
            _alter_cheap(eng)
        else:  # stage 2's commit hands its state back unchanged
            monkeypatch.setattr(engine_mod, "_commit_j",
                                lambda state, *a, **k: state)

    out = _run(spec, tiny, cell, patch=patch)
    assert not out["correct"], out["checks"]
