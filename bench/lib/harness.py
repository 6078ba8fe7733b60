"""Find the benchmark's pieces by the names in ``BENCHMARK.json``.

Every piece of a cell lives in a file of its own, named after the entry
that uses it, so that adding a configuration, a traffic mix or a metric
means adding files and entries, never editing one:

    bench/configs/<config>.json       a model configuration, as run
    bench/traffic/<mix>.json          a traffic mix's parameters; its
                                      ``kind`` names bench/generators/<kind>.py
    bench/models/<model>.py           the architecture a configuration
                                      names under ``model``: the program's
                                      ``ArchConfig``, the weights' draw
                                      rule ``FAN_IN``, ``shapes(conf)``
                                      and the counts over them
    bench/references/<reference>.py   the plain reference a configuration
                                      names under ``reference``
    bench/metrics/<metric>.py         one metric's reader, ``read(run)``
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
from typing import List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


class Harness:
    """The benchmark rooted at ``root`` (the checkout), with its pieces
    under ``bench_dir``."""

    def __init__(self, root: pathlib.Path = ROOT,
                 bench_dir: Optional[pathlib.Path] = None):
        self.root = pathlib.Path(root)
        self.bench_dir = pathlib.Path(bench_dir) if bench_dir else \
            self.root / "bench"
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)
        self._modules: dict = {}

    # ---------------------------------------------------------- entries --
    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        """The configuration file's contents, with its ``name``."""
        for c in self.spec["configs"]:
            if c["name"] == name:
                with open(self.root / c["file"]) as f:
                    conf = json.load(f)
                conf["name"] = name
                return conf
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(self.bench_dir / "traffic" / f"{name}.json") as f:
            mix = json.load(f)
        mix["name"] = name
        return mix

    def metrics(self, cell: str, trace: bool) -> List[dict]:
        """The cell's end-to-end metrics (``trace`` off) or per-layer ones
        (on): every entry whose ``workloads`` names the cell, or that has
        no ``workloads`` key."""
        group = self.spec["per_layer"] if trace else self.spec["end_to_end"]
        return [m for m in group
                if "workloads" not in m or cell in m["workloads"]]

    # ---------------------------------------------------------- modules --
    def _module(self, kind: str, name: str):
        path = self.bench_dir / kind / f"{name}.py"
        key = str(path)
        if key not in self._modules:
            if not path.exists():
                raise FileNotFoundError(f"no {kind[:-1]} module {path}")
            spec = importlib.util.spec_from_file_location(
                f"bench_{kind}_{name.replace('-', '_').replace('.', '_')}",
                path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def generator(self, mix: dict):
        return self._module("generators", mix["kind"])

    def model(self, conf: dict):
        """The configuration's model module, the only code that knows its
        architecture's sizes.  There is no default: a configuration
        that names none, or one that is missing, is refused with the
        modules there are."""
        known = sorted(p.stem for p in (self.bench_dir / "models").glob(
            "*.py"))
        name = conf.get("model")
        if name not in known:
            raise KeyError(
                f"config {conf['name']!r} names model module {name!r}; "
                f"known (bench/models/<model>.py): {known}")
        return self._module("models", name)

    def reference(self, conf: dict):
        return self._module("references", conf["reference"])

    def reader(self, metric: str):
        return self._module("metrics", metric)
