"""Everything a cell is made of, found by the names in ``BENCHMARK.json``:
the deployment (``configs/<config>/``), the traffic mix
(``traffic/<mix>.json``), the metrics (``metrics/<metric>.json`` naming a
reader in ``readers/``) and the peaks. Adding a cell, a mix, a
configuration or a metric is adding files and one entry; nothing here
names one."""

import importlib
import json
import os
import re
from typing import List

CHIP_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP_DIR))

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


class Manifest:
    """``BENCHMARK.json`` of a checkout and the files it names."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.chip_dir = os.path.join(root, "benchmarks", "chip")
        self.doc = load_json(os.path.join(root, "BENCHMARK.json"))
        self.cells = {w["name"]: w for w in self.doc["workloads"]}
        self.configs = {c["name"]: c for c in self.doc["configs"]}

    def cell(self, name: str) -> dict:
        if name not in self.cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"it has {sorted(self.cells)}")
        return self.cells[name]

    def model_dir(self, config: str) -> str:
        return os.path.dirname(
            os.path.join(self.root, self.configs[config]["file"]))

    def deployment(self, config: str) -> dict:
        return load_json(os.path.join(self.model_dir(config),
                                      "deployment.json"))

    def model_config(self, config: str) -> dict:
        return load_json(os.path.join(self.root,
                                      self.configs[config]["file"]))

    def traffic(self, mix: str) -> dict:
        return load_json(os.path.join(self.chip_dir, "traffic",
                                      f"{mix}.json"))

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(self.chip_dir, "peaks.json"))
        if device_kind not in table["by_device_kind"]:
            raise KeyError(
                f"device kind {device_kind!r} is not in peaks.json "
                f"({sorted(table['by_device_kind'])}): add it with its "
                f"source, there is no default")
        return table["by_device_kind"][device_kind]

    def metrics_of(self, cell: str, group: str) -> List[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports:
        those with no ``workloads`` key, and those that list it."""
        return [m for m in self.doc[group]
                if "workloads" not in m or cell in m["workloads"]]

    def reader(self, metric: str):
        """(read function, args) of a metric, from ``metrics/<name>.json``."""
        spec = load_json(os.path.join(self.chip_dir, "metrics",
                                      f"{metric}.json"))
        module = importlib.import_module(
            f"benchmarks.chip.readers.{spec['reader']}")
        return module.read, spec.get("args", {})


def validate(doc: dict, root: str = ROOT) -> List[str]:
    """Faults of a ``BENCHMARK.json`` against the parts of the contract a
    file can show (names, units, arrows, shares, files). Empty: none."""
    faults: List[str] = []
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    cells = {w["name"]: w for w in doc["workloads"]}
    configs = {c["name"]: c for c in doc["configs"]}
    names = [m["name"] for m in doc["end_to_end"] + doc["per_layer"]]
    for group in (names, list(cells), list(configs)):
        for name in group:
            if not NAME.match(name):
                faults.append(f"bad name {name!r}")
        if len(set(group)) != len(group):
            faults.append(f"duplicate among {sorted(group)}")
    if "setup_s" not in e2e:
        faults.append("no setup_s among end_to_end")
    for metric in doc["end_to_end"] + doc["per_layer"]:
        if not UNIT.match(metric["unit"]):
            faults.append(f"{metric['name']}: bad unit {metric['unit']!r}")
        if metric["better"] not in ("lower", "higher"):
            faults.append(f"{metric['name']}: better {metric['better']!r}")
        if metric["source"] not in SOURCES:
            faults.append(f"{metric['name']}: source {metric['source']!r}")
        for cell in metric.get("workloads", ()):
            if cell not in cells:
                faults.append(f"{metric['name']}: unknown cell {cell!r}")
        spec = os.path.join(root, "benchmarks", "chip", "metrics",
                            f"{metric['name']}.json")
        if not os.path.exists(spec):
            faults.append(f"{metric['name']}: no metrics/ file")
    for metric in doc["end_to_end"]:
        if metric["source"] not in ("host_clock", "device_trace"):
            faults.append(f"{metric['name']}: end-to-end source")
        if not 0 < metric["bound"] <= 0.1:
            faults.append(f"{metric['name']}: bound {metric['bound']}")

    def reported(metric: dict) -> set:
        return set(metric.get("workloads", cells))

    for metric in doc["per_layer"]:
        moved = e2e.get(metric["moves"])
        if moved is None:
            faults.append(f"{metric['name']}: moves unknown "
                          f"{metric['moves']!r}")
        elif not reported(metric) <= reported(moved):
            faults.append(
                f"{metric['name']}: moves {metric['moves']}, which "
                f"{sorted(reported(metric) - reported(moved))} do not report")
    for name, cell in cells.items():
        if cell["config"] not in configs:
            faults.append(f"{name}: unknown config {cell['config']!r}")
        if cell["chips"] not in (1, 4):
            faults.append(f"{name}: chips {cell['chips']}")
        if len(cell["why"]) > 200:
            faults.append(f"{name}: why longer than 200 characters")
        mine_e2e = [m for m in doc["end_to_end"] if name in reported(m)]
        mine_layer = [m for m in doc["per_layer"] if name in reported(m)]
        if len(mine_e2e) < 2 or not mine_layer:
            faults.append(f"{name}: reports too few metrics")
        mix = os.path.join(root, "benchmarks", "chip", "traffic",
                           f"{cell['traffic']}.json")
        if not os.path.exists(mix):
            faults.append(f"{name}: no traffic file {cell['traffic']}.json")
    pairs = [(c["config"], c["traffic"]) for c in cells.values()]
    if len(set(pairs)) != len(pairs):
        faults.append("a pair of configuration and traffic appears twice")
    used = {c["config"] for c in cells.values()}
    files = [c["file"] for c in configs.values()]
    if len(set(files)) != len(files):
        faults.append("two configurations share a file")
    for name, config in configs.items():
        if name not in used:
            faults.append(f"configuration {name} has no cell")
        if not any(config["file"].startswith(p.rstrip("/") + "/")
                   for p in doc["paths"]):
            faults.append(f"{name}: file outside paths")
        if not os.path.exists(os.path.join(root, config["file"])):
            faults.append(f"{name}: no file {config['file']}")
    four = sum(1 for c in cells.values() if c["chips"] == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} four-chip cells of {len(cells)}: at most a "
                      f"quarter, rounded down, and one always")
    if not 1 <= doc["run_seconds"] <= 51:
        faults.append(f"run_seconds {doc['run_seconds']}")
    return faults
