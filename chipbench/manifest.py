"""`BENCHMARK.json` and the data files it names, found by name.

A cell names a configuration (its ``file``) and a traffic mix
(``<path>/traffic/<name>.json`` under any of ``paths``); a configuration
names its family (``<path>/families/<name>.py``: the architecture's
weights, reference and counts); a per-layer metric names its reader
(``<path>/layer_metrics/<name>.py``).  Nothing here knows a particular
cell or architecture, so a later PR adds one by adding files and entries.
"""

from __future__ import annotations

import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    family: object                 # the module of the configuration's family
    end_to_end: list[dict]
    per_layer: list[dict]


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


class Manifest:
    def __init__(self, root: Path):
        self.root = Path(root)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    def _find(self, sub: str, name: str, suffixes: tuple[str, ...]) -> Path:
        for path in self.doc["paths"]:
            for suffix in suffixes:
                cand = self.root / path / sub / f"{name}{suffix}"
                if cand.is_file():
                    return cand
        raise FileNotFoundError(
            f"no {sub}/{name}{suffixes} under any of {self.doc['paths']}"
        )

    def cell(self, workload: str) -> Cell:
        entry = next((w for w in self.doc["workloads"] if w["name"] == workload), None)
        if entry is None:
            known = [w["name"] for w in self.doc["workloads"]]
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json (known: {known})")
        cfg = next(c for c in self.doc["configs"] if c["name"] == entry["config"])
        config = json.loads((self.root / cfg["file"]).read_text())
        traffic = json.loads(self._find("traffic", entry["traffic"], (".json",)).read_text())
        return Cell(
            name=workload, chips=int(entry["chips"]), config_name=cfg["name"],
            config=config, traffic_name=entry["traffic"], traffic=traffic,
            family=self.family(config["family"]),
            end_to_end=[m for m in self.doc["end_to_end"] if _applies(m, workload)],
            per_layer=[m for m in self.doc["per_layer"] if _applies(m, workload)],
        )

    def _module(self, sub: str, name: str):
        """The module in ``<path>/<sub>/<name>.py``, loaded by its path (a
        name may hold dots and dashes)."""
        path = self._find(sub, name, (".py",))
        spec = importlib.util.spec_from_file_location(
            f"chipbench_{sub}_" + re.sub(r"\W", "_", name), path
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module

    def family(self, name: str):
        """The family file ``<path>/families/<name>.py``, as a module."""
        return self._module("families", name)

    def families(self) -> list:
        """Every family file under any of ``paths``."""
        found = {p.stem for path in self.doc["paths"]
                 for p in (self.root / path / "families").glob("*.py")}
        return [self.family(name) for name in sorted(found - {"__init__"})]

    def reader(self, metric_name: str):
        """The ``read(run)`` function of a per-layer metric's own file."""
        return self._module("layer_metrics", metric_name).read

    def problems(self) -> list[str]:
        """What the contract's rules on names, units and references would
        refuse (an empty list is a clean manifest)."""
        d, bad = self.doc, []
        names = lambda xs: [x["name"] for x in xs]  # noqa: E731
        for group in ("configs", "workloads", "end_to_end", "per_layer"):
            for n in names(d[group]):
                if not NAME.match(n):
                    bad.append(f"{group}: name {n!r}")
            if len(set(names(d[group]))) != len(d[group]):
                bad.append(f"{group}: duplicate names")
        if set(names(d["end_to_end"])) & set(names(d["per_layer"])):
            bad.append("a metric name is both end-to-end and per-layer")
        for m in d["end_to_end"] + d["per_layer"]:
            if not UNIT.match(m["unit"]):
                bad.append(f"{m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                bad.append(f"{m['name']}: better {m['better']!r}")
        e2e = {m["name"]: m for m in d["end_to_end"]}
        cells = set(names(d["workloads"]))
        for w in d["workloads"]:
            if not NAME.match(w["traffic"]) or w["config"] not in names(d["configs"]):
                bad.append(f"workload {w['name']}: config or traffic")
            if w["chips"] not in (1, 4):
                bad.append(f"workload {w['name']}: chips {w['chips']}")
        for m in d["per_layer"]:
            target = e2e.get(m["moves"])
            if target is None:
                bad.append(f"{m['name']}: moves unknown metric {m['moves']!r}")
                continue
            mine = set(m.get("workloads", cells))
            theirs = set(target.get("workloads", cells))
            if not mine <= cells or not mine <= theirs:
                bad.append(f"{m['name']}: a cell does not report {m['moves']}")
        if "setup_s" not in e2e:
            bad.append("no setup_s")
        return bad
