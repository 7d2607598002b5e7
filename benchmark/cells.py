"""Finding a cell's files by the names ``BENCHMARK.json`` gives.

A cell is ``workloads/<cell>.json`` (its traffic mix: statements, loop,
clients, bindings, limits) over ``configs/<config>.json`` (tables, conf,
guarantees).  A statement is ``sql/<config>/<stmt>.sql`` with its plain
reference ``reference/<config>/<stmt>.py``; a metric is
``metrics/<name>.py`` with one ``read(run)`` function.  Nothing here
knows a cell, a statement or a metric by name.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)


def _json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Statement:
    def __init__(self, config: str, entry: dict):
        self.name = entry["name"]
        self.weight = int(entry.get("weight", 1))
        self.mode = entry.get("mode", "sql")          # sql | prepared
        self.params = entry.get("params", {})         # name -> SQL type
        self.bindings = entry.get("bindings", {})     # name -> how drawn
        with open(os.path.join(HERE, "sql", config,
                               f"{self.name}.sql")) as f:
            self.sql = " ".join(f.read().split())
        self.reference = _module(
            os.path.join(HERE, "reference", config, f"{self.name}.py"),
            f"reference_{self.name}")
        self.spec = self.reference.SPEC


class Cell:
    def __init__(self, name: str):
        bench = _json(CHECKOUT, "BENCHMARK.json")
        entry = next((w for w in bench["workloads"] if w["name"] == name),
                     None)
        if entry is None:
            raise SystemExit(
                f"benchmark: no cell {name!r} in BENCHMARK.json "
                f"(cells: {[w['name'] for w in bench['workloads']]})")
        self.name = name
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.config = _json(HERE, "configs", f"{self.config_name}.json")
        self.workload = _json(HERE, "workloads", f"{name}.json")
        self.tables = {t: self.config["tables"][t] for t in
                       self.workload.get("tables", self.config["tables"])}
        self.conf = {**self.config.get("conf", {}),
                     **self.workload.get("conf", {})}
        self.statements = [Statement(self.config_name, s)
                           for s in self.workload["statements"]]
        self.limits = self.workload["limits"]
        self.metrics = {
            "end_to_end": [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])],
            "per_layer": [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]}

    def scaled_tables(self, scale: float) -> dict:
        """The cell's tables with the fact tables' rows cut by
        ``scale`` (the CPU rehearsal; dimensions keep their rows so
        every key still joins)."""
        if scale >= 1:
            return self.tables
        fact = max(self.tables, key=lambda t: self.tables[t]["rows"])
        return {t: {**s, "rows": max(2000, int(s["rows"] * scale))}
                if t == fact else s for t, s in self.tables.items()}


def reader(metric: str):
    """``metrics/<name>.py``'s ``read``."""
    path = os.path.join(HERE, "metrics", f"{metric}.py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: metric {metric!r} has no reader "
                         f"at {path}")
    return _module(path, f"metric_{metric.replace('.', '_')}").read


def peaks(device_kind: str) -> dict:
    table = _json(HERE, "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return table[device_kind]
