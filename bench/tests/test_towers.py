"""Tower modules: a configuration's towers are files found by name.

The dense module (``bench/configs/dense_gqa.py``) must make the weights the
harness made before towers were files, bit for bit. A tower the harness
has never seen (``data/tiny_mla.py``, latent attention, with its plain
reference ``data/tiny_mla_ref.py`` and configuration
``data/tiny-mla.json``) must run a whole cell once those files are copied
into a benchmark's ``configs/`` and its ``BENCHMARK.json`` names them:
nothing under ``harness/`` changes for it.
"""
from __future__ import annotations

import json
import shutil
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import BENCH, ROOT, TINY_CHEAP, TINY_EXPENSIVE, TINY_MIX
from test_correct import _alter_answers

from harness import flops, model, peaks
from harness.cell import run_cell
from harness.spec import Spec, config_module
from harness.towers import RowCounts

DATA = BENCH / "tests" / "data"
DENSE_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab", "embed_dim", "rope_theta", "dtype")
SEED = 2**33 + 41


def _frozen_dense_params_fn(t: dict):
    """The dense tower's weights as the harness first drew them, frozen
    here as the oracle of ``dense_gqa.params_fn``."""
    dtype = jnp.dtype(t["dtype"])
    d, h, hk, hd = t["d_model"], t["n_heads"], t["n_kv_heads"], t["head_dim"]
    f, n_l = t["d_ff"], t["n_layers"]

    def normal(k, shape, std):
        return (jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype))

    def build(key):
        ks = jax.random.split(key, 10)
        lay = (n_l,)
        blocks = {
            "attn": {
                "wq": normal(ks[0], lay + (d, h * hd), d ** -0.5),
                "wk": normal(ks[1], lay + (d, hk * hd), d ** -0.5),
                "wv": normal(ks[2], lay + (d, hk * hd), d ** -0.5),
                "wo": normal(ks[3], lay + (h * hd, d), (h * hd) ** -0.5),
            },
            "ffn": {
                "w_gate": normal(ks[4], lay + (d, f), d ** -0.5),
                "w_up": normal(ks[5], lay + (d, f), d ** -0.5),
                "w_down": normal(ks[6], lay + (f, d), f ** -0.5),
            },
            "ln1": jnp.ones(lay + (d,), dtype),
            "ln2": jnp.ones(lay + (d,), dtype),
        }
        return {
            "embed": normal(ks[7], (t["vocab"], d), 0.02),
            "final_norm": jnp.ones((d,), dtype),
            "dense_blocks": blocks,
            "embed_head": normal(ks[8], (d, t["embed_dim"]), d ** -0.5),
        }

    return build


def _shapes(tree):
    return jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), tree)


@pytest.mark.parametrize("which", ["expensive", "cheap"])
def test_dense_module_draws_the_frozen_weights(spec, which):
    """Bit-equal at the test sizes; equal in shape and dtype at the
    ``scidocs-sfr7b`` sizes, whose sizes are the dense keys as before."""
    cfg = spec.load_config("scidocs-sfr7b")
    t = model.tower_dict(cfg, which, BENCH)
    group = cfg if which == "expensive" else cfg["cheap_tower"]
    assert t == {**{k: group[k] for k in DENSE_KEYS}, "tower": "dense_gqa"}
    key = model.seed_key(SEED)
    full = jax.eval_shape(model.params_fn(t), key)
    assert _shapes(full) == _shapes(jax.eval_shape(
        _frozen_dense_params_fn(t), key))
    tiny = {**t, **(TINY_EXPENSIVE if which == "expensive" else TINY_CHEAP)}
    got = model.make_params(key, tiny)
    want = jax.jit(_frozen_dense_params_fn(tiny))(key)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def mla_spec(tmp_path_factory) -> Spec:
    """A benchmark holding this one's files, the tiny MLA tower's three
    files in its ``configs/``, and a cell of them in its BENCHMARK.json."""
    root = tmp_path_factory.mktemp("mla")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for name in ("tiny_mla.py", "tiny_mla_ref.py", "tiny-mla.json"):
        shutil.copy(DATA / name, root / "bench" / "configs" / name)
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append({
        "name": "tiny-mla", "source": "a test configuration",
        "file": "bench/configs/tiny-mla.json", "reduced": [],
        "why": "a tower the harness was not written for"})
    doc["workloads"].append({
        "name": "tiny-mla.batch", "config": "tiny-mla", "traffic": "batch-8",
        "chips": 1, "why": "the tiny MLA towers under lock-step batches"})
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return Spec(root)


def _tiny_mla(spec: Spec, which: str) -> dict:
    return model.tower_dict(spec.load_config("tiny-mla"), which, spec.bench)


def test_tiny_mla_weights_have_the_program_layout(mla_spec):
    """``params_fn`` makes the tree ``transformer.init_params`` would for
    the module's ``program_config``: the same leaves, shapes and dtypes."""
    from repro.models import transformer as T

    for which in ("expensive", "cheap"):
        t = _tiny_mla(mla_spec, which)
        key = jax.random.key(0)
        mine = jax.eval_shape(model.params_fn(t), key)
        prog = jax.eval_shape(
            lambda k: T.init_params(k, model.program_config(t, which)), key)
        assert _shapes(mine) == _shapes(prog)


@pytest.mark.parametrize("seq", [16, 64])
def test_tiny_mla_row_flops_matches_xla_but_masked_attention(mla_spec, seq):
    """As ``test_flops`` holds the dense count: XLA's count of the program
    exceeds the module's by the masked attention it computes, the key axis
    padded to ``block_kv``, within the elementwise operations XLA also
    counts. One layer, since XLA counts a scan's body once."""
    from repro.models import transformer as T

    t = dict(_tiny_mla(mla_spec, "expensive"), n_layers=1, d_model=256,
             n_heads=4, q_lora_rank=64, kv_lora_rank=64, qk_nope_dim=32,
             qk_rope_dim=16, v_head_dim=32, d_ff=512, dtype="float32")
    rows, block_kv = 8, 512
    cfg = model.program_config(t, "t")
    params = jax.eval_shape(model.params_fn(t), jax.random.key(0))
    compiled = jax.jit(lambda p, x: T.embed_pool(p, x, cfg)).lower(
        params, jax.ShapeDtypeStruct((rows, seq), jnp.int32)).compile()
    xla = compiled.cost_analysis()["flops"]
    mine = rows * flops.row_flops(t, seq)
    per_pair = 2 * t["n_heads"] * (t["qk_nope_dim"] + t["qk_rope_dim"]
                                   + t["v_head_dim"])
    masked = rows * per_pair * (seq * block_kv - seq * (seq + 1) / 2)
    assert 1.0 <= (xla - mine) / masked <= 1.1


@pytest.mark.parametrize("fault", [None, "answer_altered"])
def test_a_tower_added_by_files_runs_a_cell(mla_spec, tmp_path, fault):
    """A sound run of the new cell is correct against its own reference,
    with tokens drawn below the smaller tower's vocabulary; one whose
    drains alter the documents' embeddings is not."""
    spec = mla_spec
    seen = {}

    def patch(eng):
        seen["max_token"] = int(eng.corpus_tokens.max())
        if fault:
            _alter_answers(eng)

    mix = {**spec.load_traffic("batch-8"), **TINY_MIX}
    out = run_cell(spec, "tiny-mla.batch", SEED, 2.0, False,
                   t_start=time.perf_counter(),
                   trace_dir=tmp_path / ".bench_trace", mix=mix, patch=patch)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["correct"] is (fault is None), out["checks"]
    assert seen["max_token"] < _tiny_mla(spec, "cheap")["vocab"]


def test_serve_mfu_counts_the_tower_module_operations(mla_spec):
    """``serve_mfu`` reads each tower's operations from its own module."""
    spec = mla_spec
    cfg = spec.load_config("tiny-mla")
    mod = config_module(spec.bench, "tiny_mla")
    sizes = {k: model.tower_dict(cfg, k, spec.bench)
             for k in ("cheap", "expensive")}
    seq = cfg["query_len"]
    rows = {"cheap": 40, "expensive": 300}
    ctx = {"towers": {k: RowCounts(useful=n) for k, n in rows.items()},
           "tower_sizes": {k: (sizes[k], seq) for k in sizes},
           "window_s": 2.0, "device_kind": "TPU v5 lite"}
    ops = sum(n * mod.row_flops(sizes[k], seq) for k, n in rows.items())
    got = spec.reader("serve_mfu")(ctx)
    peak = peaks.peaks("TPU v5 lite")["bf16_flops_per_s"]
    assert got == pytest.approx(100.0 * ops / (2.0 * peak), rel=1e-12)
    assert all(flops.row_flops(sizes[k], seq) == mod.row_flops(sizes[k], seq)
               for k in sizes)
