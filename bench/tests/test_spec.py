"""The harness finds every configuration, mix and metric by name."""
from __future__ import annotations

import json
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, ROOT

from harness.spec import Spec, check_name, tower_group, tower_name


def test_every_name_resolves(spec):
    for name, w in spec.cells.items():
        assert spec.config_path(w["config"]).is_file()
        assert spec.traffic_path(w["traffic"]).is_file()
        cfg = spec.load_config(w["config"])
        assert cfg["name"] == w["config"]
        assert (BENCH / "configs" / f"{cfg['reference']}.py").is_file()
        for which in ("expensive", "cheap"):
            module = tower_name(tower_group(cfg, which))
            assert spec.module_path(module).is_file()
        for kind in ("end_to_end", "per_layer"):
            names = spec.cell_metrics(name, kind)
            assert names, (name, kind)
            for m in names:
                assert callable(spec.reader(m))
        assert "setup_s" in spec.cell_metrics(name, "end_to_end")


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", ".hidden", "", "x" * 65,
                                 "café"])
def test_forbidden_names_are_refused(bad):
    with pytest.raises(ValueError):
        check_name(bad, "metric")


def _copy(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    return doc


def test_unresolved_metric_is_refused(tmp_path):
    doc = _copy(tmp_path)
    doc["per_layer"].append({**doc["per_layer"][0], "name": "no_such_metric"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(FileNotFoundError, match="no_such_metric"):
        Spec(tmp_path)


def test_unresolved_traffic_is_refused(tmp_path):
    doc = _copy(tmp_path)
    doc["workloads"][0]["traffic"] = "no-such-mix"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(FileNotFoundError, match="no-such-mix"):
        Spec(tmp_path)


@pytest.mark.parametrize("group, key, module, body", [
    (None, "tower", "no_such_tower", None),
    ("cheap_tower", "tower", "no_such_cheap_tower", None),
    (None, "reference", "no_such_reference", None),
    (None, "tower", "half_tower", "KEYS = ()\n"),
])
def test_unresolved_module_is_refused(tmp_path, group, key, module, body):
    """A configuration naming a tower or reference module that is not
    there, or lacks what the harness calls, is refused as the benchmark is
    read, before any run."""
    doc = _copy(tmp_path)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    configs = tmp_path / "bench" / "configs"
    if body is not None:
        (configs / f"{module}.py").write_text(body)
    path = configs / "scidocs-sfr7b.json"
    cfg = json.loads(path.read_text())
    (cfg[group] if group else cfg)[key] = module
    path.write_text(json.dumps(cfg))
    with pytest.raises(FileNotFoundError, match=module):
        Spec(tmp_path)


def test_forbidden_workload_name_is_refused(tmp_path):
    doc = _copy(tmp_path)
    doc["workloads"][0]["name"] = "scidocs batch"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    with pytest.raises(ValueError):
        Spec(tmp_path)


def test_no_tpu_exits_nonzero_with_no_result(spec):
    cell = next(iter(spec.cells))
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
           "HOME": str(ROOT)}
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", cell,
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=ROOT, env=env, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_bare_benchmark_directory_exits_nonzero(tmp_path, spec):
    """A directory holding only BENCHMARK.json and bench/ has no program."""
    _copy(tmp_path)
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", next(iter(spec.cells)),
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, cwd=tmp_path,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path)}, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
