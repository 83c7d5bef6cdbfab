"""``BENCHMARK.json`` and the files it names.

Every configuration, traffic mix and metric is found by its name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py``. A configuration names its plain reference
(``"reference"``) and, in each tower group, its tower's architecture
(``"tower"``; the dense tower where a group names none), each a module
``bench/configs/<module>.py``. A later change adds a cell, a
configuration, a tower or a metric by adding files and entries; no file
here needs an edit.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")

DENSE_TOWER = "dense_gqa"  # the tower module of a group that names none
TOWER_API = ("KEYS", "params_fn", "program_config", "row_flops")
REFERENCE_API = ("embed",)


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"{what} name {name!r} is not 1-64 of "
                         "[A-Za-z0-9_.-] starting with [A-Za-z0-9_]")
    return name


def tower_group(cfg: dict, which: str) -> dict:
    """The group of configuration ``cfg`` that states a tower: the file's
    top level for ``"expensive"`` (D), its ``cheap_tower`` for ``"cheap"``
    (d)."""
    return cfg if which == "expensive" else cfg["cheap_tower"]


def tower_name(group: dict) -> str:
    """The tower module a tower group names."""
    return group.get("tower", DENSE_TOWER)


def config_module(bench: Path, name: str):
    """``<bench>/configs/<name>.py``, a tower module or a plain reference,
    executed once in a process: a later call by the same name returns that
    module, whichever directory it names."""
    key = f"_bench_config_{check_name(name, 'module')}"
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(
            key, Path(bench) / "configs" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


class Spec:
    """The parsed benchmark, with each name resolved to its file."""

    def __init__(self, root: Path, bench_dir: Path | None = None):
        self.root = Path(root)
        self.bench = Path(bench_dir) if bench_dir else self.root / "bench"
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.configs = {check_name(c["name"], "config"): c
                        for c in self.doc["configs"]}
        self.cells = {check_name(w["name"], "workload"): w
                      for w in self.doc["workloads"]}
        self.metrics = {}
        for kind in ("end_to_end", "per_layer"):
            for m in self.doc[kind]:
                self.metrics[check_name(m["name"], "metric")] = {
                    **m, "kind": kind}
        self.resolve_all()

    def config_path(self, name: str) -> Path:
        return self.bench / "configs" / f"{check_name(name, 'config')}.json"

    def traffic_path(self, name: str) -> Path:
        return self.bench / "traffic" / f"{check_name(name, 'traffic')}.json"

    def metric_path(self, name: str) -> Path:
        return self.bench / "metrics" / f"{check_name(name, 'metric')}.py"

    def module_path(self, name: str) -> Path:
        return self.bench / "configs" / f"{check_name(name, 'module')}.py"

    def resolve_all(self) -> None:
        """Fail on any name that does not lead to its file."""
        missing = []
        for w in self.cells.values():
            if w["config"] not in self.configs:
                missing.append(f"cell {w['name']}: config {w['config']}")
            check_name(w["traffic"], "traffic")
            if not self.traffic_path(w["traffic"]).is_file():
                missing.append(str(self.traffic_path(w["traffic"])))
        for c in self.configs.values():
            p = self.root / c["file"]
            if p != self.config_path(c["name"]) or not p.is_file():
                missing.append(f"config {c['name']}: {c['file']}")
                continue
            missing += self.resolve_modules(c["name"],
                                            json.loads(p.read_text()))
        for name in self.metrics:
            if not self.metric_path(name).is_file():
                missing.append(str(self.metric_path(name)))
        if missing:
            raise FileNotFoundError("unresolved names: " + "; ".join(missing))

    def resolve_modules(self, config: str, cfg: dict) -> list[str]:
        """Load the configuration's reference and each tower group's
        module; what is missing, or lacks a function the harness calls."""
        wanted = [("reference", cfg.get("reference", ""), REFERENCE_API)]
        wanted += [(f"{w} tower", tower_name(tower_group(cfg, w)), TOWER_API)
                   for w in ("expensive", "cheap")]
        missing = []
        for role, name, api in wanted:
            path = self.module_path(name)
            if not path.is_file():
                missing.append(f"config {config}: {role} {path}")
                continue
            mod = config_module(self.bench, name)
            lacks = [a for a in api if not hasattr(mod, a)]
            if lacks:
                missing.append(f"config {config}: {role} {path} lacks "
                               + ", ".join(lacks))
        return missing

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json: "
                           f"{sorted(self.cells)}")
        return self.cells[name]

    def cell_metrics(self, name: str, kind: str) -> list[str]:
        """Metrics of ``kind`` that cell ``name`` reports."""
        return [m for m, d in self.metrics.items() if d["kind"] == kind
                and name in d.get("workloads", self.cells)]

    def load_config(self, name: str) -> dict:
        return json.loads(self.config_path(name).read_text())

    def load_traffic(self, name: str) -> dict:
        return json.loads(self.traffic_path(name).read_text())

    def reader(self, name: str):
        """The ``read(ctx)`` function of metric ``name``."""
        spec = importlib.util.spec_from_file_location(
            "_bench_metric_" + name.replace(".", "_").replace("-", "_"),
            self.metric_path(name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read
