"""``BENCHMARK.json`` and the files it names, found by name.

Everything one cell is lives in files of its own under the benchmark's
directory, so a later change adds a cell, a traffic mix, a configuration
or a metric by adding files and entries, never by editing one:

* ``configs/<config>.json``   a deployment: its sizes, source and limits;
* ``traffic/<traffic>.json``  a traffic mix: its driver and parameters;
* ``drivers/<driver>.py``     one per deployment kind, ``run(ctx) -> dict``;
* ``metrics/<metric>.py``     one reader per metric, ``read(run) -> float
  or None``;
* ``refs/<reference>.py``     a configuration's plain reference.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


class Bench:
    """The benchmark rooted at ``root`` (the directory of BENCHMARK.json)."""

    def __init__(self, root=ROOT):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())
        self.dir = self.root / self.doc["paths"][0]
        self._modules: Dict[str, object] = {}

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {[w['name'] for w in self.doc['workloads']]})")

    def config(self, name: str) -> dict:
        return json.loads((self.dir / "configs" / f"{name}.json").read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def module(self, kind: str, name: str):
        """Import ``<kind>/<name>.py`` (names may hold dots)."""
        path = self.dir / kind / f"{name}.py"
        key = str(path)
        if key not in self._modules:
            if not path.is_file():
                raise FileNotFoundError(f"no {kind[:-1]} file {path}")
            mod_name = f"_bench_{kind}_" + re.sub(r"\W", "_", name)
            spec = importlib.util.spec_from_file_location(mod_name, path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[mod_name] = mod
            spec.loader.exec_module(mod)
            self._modules[key] = mod
        return self._modules[key]

    def metrics_for(self, cell: str, group: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    def cell(self, name: str) -> dict:
        """Everything one run of a cell needs, found by name: the
        workload entry, its configuration and its traffic mix."""
        w = self.workload(name)
        traffic = self.traffic(w["traffic"])
        if traffic.get("config", w["config"]) != w["config"]:
            raise ValueError(f"{name}: traffic {w['traffic']!r} is for "
                             f"{traffic['config']!r}, not {w['config']!r}")
        if traffic.get("chips", w["chips"]) != w["chips"]:
            raise ValueError(f"{name}: traffic asks {traffic['chips']} "
                             f"chips, the workload {w['chips']}")
        return {"workload": w, "config": self.config(w["config"]),
                "traffic": traffic}
