"""Finds a cell's files by the names in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, statement,
reference or per-layer metric is a file of its own under the
benchmark's directory; a new cell is new files plus new entries and no
edit here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/<kind>/<name>.py as a module (a reference or a
    per-layer metric's reader)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass(frozen=True)
class Statement:
    """One (statement id, variant) of a traffic file: the text sent."""

    sid: str            # the traffic file's id, e.g. q1_sf10
    template: str       # statements/<template>.sql, references/<template>.py
    catalog: str
    klass: str
    variant: int
    params: Dict[str, str]
    sql: str

    @property
    def key(self) -> str:
        return f"{self.sid}#{self.variant}"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]      # the manifest entries this cell reports
    per_layer: List[Dict]
    statements: Dict[str, List[Statement]]   # sid -> its variants

    @property
    def every(self) -> List[Statement]:
        """Every variant of every statement of the traffic file."""
        return [st for variants in self.statements.values()
                for st in variants]

    @property
    def concurrent(self) -> bool:
        """Whether the configuration selects the concurrent server
        (per-query runners, several statements on the chip at once)."""
        return "query.max-memory-bytes" in self.config["config_properties"]


def _reports(metric: Dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str) -> Cell:
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(
        (w for w in manifest["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json (has: "
            f"{[w['name'] for w in manifest['workloads']]})")
    config_entry = next(
        c for c in manifest["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(
        BENCH_DIR, "traffic", f"{entry['traffic']}.json"))
    statements: Dict[str, List[Statement]] = {}
    for sid, st in traffic["statements"].items():
        with open(os.path.join(
                BENCH_DIR, "statements", f"{st['template']}.sql")) as f:
            text = f.read()
        if st["catalog"] not in config["catalogs"]:
            raise KeyError(
                f"{sid}: catalog {st['catalog']!r} is not in "
                f"configuration {config['name']!r}")
        statements[sid] = [
            Statement(sid, st["template"], st["catalog"], st["class"],
                      i, dict(params), text.format(**params))
            for i, params in enumerate(st["variants"])
        ]
    end_to_end = [m for m in manifest["end_to_end"]
                  if _reports(m, name)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in manifest["per_layer"]
                 if _reports(m, name) and m["moves"] in e2e_names]
    return Cell(name, int(entry["chips"]), config, traffic,
                end_to_end, per_layer, statements)


def load_peaks(device_kind: str) -> Dict:
    peaks = load_json(os.path.join(BENCH_DIR, "peaks.json"))["devices"]
    if device_kind not in peaks:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(has: {sorted(peaks)}): a device without published peaks "
            "is an error, not a default")
    return peaks[device_kind]
