"""Per-architecture smoke tests (deliverable f): REDUCED config of the same
family, one forward/train step on CPU, asserting output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch
from repro.models import gnn, recsys as R, transformer as T

KEY = jax.random.PRNGKey(0)

LM_ARCHS = ["qwen3-0.6b", "granite-20b", "deepseek-coder-33b",
            "granite-moe-3b-a800m", "deepseek-v3-671b"]


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_train_step(arch):
    spec = get_arch(arch)
    cfg = spec.make_config(True)
    params = spec.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab)
    loss, metrics = T.loss_fn(
        params, {"tokens": toks, "labels": jnp.roll(toks, -1, 1)}, cfg)
    assert np.isfinite(float(loss)), arch
    out = T.forward(params, toks, cfg)
    assert out.logits.shape == (2, 16, cfg.vocab)
    assert not bool(jnp.isnan(out.logits.astype(jnp.float32)).any())


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_decode(arch):
    spec = get_arch(arch)
    cfg = spec.make_config(True)
    params = spec.init_params(KEY, cfg)
    toks = jax.random.randint(KEY, (2, 9), 0, cfg.vocab)
    _, cache = T.prefill(params, toks[:, :8], cfg, max_seq=12)
    logits, cache = T.decode_step(params, toks[:, 8:9], cache, cfg)
    assert logits.shape == (2, 1, cfg.vocab)
    assert int(cache.length) == 9
    assert not bool(jnp.isnan(logits.astype(jnp.float32)).any())


def test_gat_smoke_all_shapes():
    from repro.configs import gat_cora
    for shape in gat_cora.SMOKE_SHAPES:
        cell = gat_cora.build_gnn_cell(None, shape, smoke=True)
        info = gat_cora.SMOKE_SHAPES[shape]
        cfg = gnn.GATConfig(d_in=info["d_feat"], n_classes=info["n_classes"])
        p = gnn.init_params(KEY, cfg)
        n, e = info["n_nodes"], info["n_edges"]
        batch = {
            "feats": jax.random.normal(KEY, (n, info["d_feat"])),
            "src": jax.random.randint(KEY, (e,), 0, n),
            "dst": jax.random.randint(jax.random.fold_in(KEY, 1), (e,), 0, n),
        }
        if info["task"] == "graph":
            ng = info["n_graphs"]
            batch["graph_ids"] = jnp.repeat(
                jnp.arange(ng), n // ng)[:n].astype(jnp.int32)
            batch["graph_labels"] = jax.random.randint(
                KEY, (ng,), 0, info["n_classes"])
        else:
            batch["labels"] = jax.random.randint(KEY, (n,), 0,
                                                 info["n_classes"])
            batch["mask"] = jnp.ones((n,), jnp.float32)
        loss, _ = gat_cora.graph_loss(
            p, batch, cfg, task=info["task"],
            n_graphs=info.get("n_graphs") or 0)
        assert np.isfinite(float(loss)), shape


RS = {
    "bst": lambda cfg: {
        "hist": jax.random.randint(KEY, (4, cfg.seq_len), 0, cfg.vocab),
        "target": jax.random.randint(KEY, (4,), 0, cfg.vocab),
        "label": jnp.ones((4,), jnp.float32)},
    "din": lambda cfg: {
        "hist": jax.random.randint(KEY, (4, cfg.seq_len), 0, cfg.vocab),
        "target": jax.random.randint(KEY, (4,), 0, cfg.vocab),
        "label": jnp.zeros((4,), jnp.float32)},
    "bert4rec": lambda cfg: {
        "items": jax.random.randint(KEY, (4, cfg.seq_len), 0, cfg.vocab),
        "mask_pos": jax.random.randint(KEY, (4, cfg.n_masked), 0, cfg.seq_len),
        "mask_labels": jax.random.randint(KEY, (4, cfg.n_masked), 0, cfg.vocab)},
    "xdeepfm": lambda cfg: {
        "fields": jax.random.randint(KEY, (4, cfg.n_fields), 0,
                                     cfg.field_vocab),
        "label": jnp.ones((4,), jnp.float32)},
}

LOSS = {"bst": R.bst_loss, "din": R.din_loss, "bert4rec": R.bert4rec_loss,
        "xdeepfm": R.xdeepfm_loss}


@pytest.mark.parametrize("arch", list(RS))
def test_recsys_smoke_train(arch):
    spec = get_arch(arch)
    cfg = spec.make_config(True)
    p = spec.init_params(KEY, cfg)
    batch = RS[arch](cfg)
    loss, _ = LOSS[arch](p, batch, cfg)
    assert np.isfinite(float(loss)), arch
    # grads flow into the embedding table
    g = jax.grad(lambda pp: LOSS[arch](pp, batch, cfg)[0])(p)
    leaves = [float(jnp.abs(l.astype(jnp.float32)).sum())
              for l in jax.tree.leaves(g)]
    assert sum(leaves) > 0


def test_all_archs_have_four_shapes():
    assert len(ARCHS) == 10
    for name, spec in ARCHS.items():
        assert len(spec.shapes) == 4, name
    from repro.configs import all_cells
    assert len(all_cells()) == 40


def test_smoke_cells_lower_on_host_mesh():
    """Every cell's step function lowers with the SMOKE config on a 1-device
    mesh — catches abstract-args/step signature mismatches cheaply."""
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    for arch in ["qwen3-0.6b", "bst", "bert4rec"]:
        spec = get_arch(arch)
        for shape in spec.shapes:
            cfg = spec.make_config(True)
            cell = spec.build_cell(cfg, shape)
            args = cell.abstract_args(mesh)
            with mesh:
                jax.jit(cell.fn, donate_argnums=cell.donate).lower(*args)
