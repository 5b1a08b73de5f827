"""BENCHMARK.json and the files it names.

Everything that belongs to one configuration, one traffic mix, one cell or
one metric is a file found by the NAME in the manifest:

    configs/<config>.json    the sizes as run, with source and departures
    traffic/<traffic>.json   the parameters of the load ("kind" picks cells/<kind>.py)
    workloads/<cell>.json    the system's settings for this cell
    metrics/<metric>.json    optional: the reader (readers/<reader>.py) and its
                             arguments; without a file a metric is the fact of
                             the same name that the cell's run recorded

so a later PR adds a cell or a metric with new files and new entries only.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent.parent
ROOT = BENCH_DIR.parent
MANIFEST = ROOT / "BENCHMARK.json"

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def load_manifest(path=MANIFEST) -> dict:
    """The manifest at `path`. A rehearsal manifest (tiny cells for the CPU)
    lists only configs and workloads and takes the metrics of the real one;
    each of its cells says which real cell it `stands_for`."""
    path = Path(path)
    with open(path) as f:
        manifest = json.load(f)
    if "end_to_end" not in manifest:
        with open(MANIFEST) as f:
            real = json.load(f)
        for key in ("run_seconds", "end_to_end", "per_layer"):
            manifest[key] = real[key]
    return manifest


def read_named(kind: str, name: str, bench_dir=BENCH_DIR, required=True):
    """<bench_dir>/<kind>/<name>.json, the one place a name is turned into
    a path."""
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} has characters outside "
                         "letters, digits, '_', '.', '-'")
    path = Path(bench_dir) / kind / f"{name}.json"
    if not path.exists():
        if required:
            raise FileNotFoundError(f"{kind} {name!r}: no file {path}")
        return None
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict      # configs/<config>.json
    traffic: dict     # traffic/<traffic>.json, cell's "traffic" overrides on top
    system: dict      # workloads/<cell>.json
    end_to_end: list  # manifest entries reported in this cell
    per_layer: list


def metrics_for(manifest: dict, group: str, cell_name: str) -> list:
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def load_cell(manifest: dict, name: str, bench_dir=BENCH_DIR) -> Cell:
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"workload {name!r} is not in the manifest "
                       f"({[w['name'] for w in manifest['workloads']]})")
    entry = entries[0]
    if entry["config"] not in [c["name"] for c in manifest["configs"]]:
        raise KeyError(f"cell {name!r}: config {entry['config']!r} is not "
                       "in the manifest")
    system = read_named("workloads", name, bench_dir)
    traffic = dict(read_named("traffic", entry["traffic"], bench_dir))
    traffic.update(system.get("traffic", {}))
    listed_as = entry.get("stands_for", name)
    return Cell(name=name, chips=int(entry["chips"]),
                config_name=entry["config"], traffic_name=entry["traffic"],
                config=read_named("configs", entry["config"], bench_dir),
                traffic=traffic, system=system,
                end_to_end=metrics_for(manifest, "end_to_end", listed_as),
                per_layer=metrics_for(manifest, "per_layer", listed_as))
