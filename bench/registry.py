"""Finds what ``BENCHMARK.json`` names, by name, under ``bench/``.

* a cell (``workloads`` entry) -> its configuration and traffic entries;
* a configuration -> ``bench/configs/<name>.json`` (the file the manifest
  gives) and its reference, ``bench/references/<reference>.py``;
* a traffic mix -> ``bench/traffic/<name>.json``;
* a cell's correctness limits -> ``bench/checks/<cell>.json``;
* a metric -> ``bench/metrics/<name>.py``, whose ``read(run)`` returns the
  number or None.

Adding a cell, a mix, a configuration or a metric adds files and
manifest entries; no file here changes.  An unknown name is an error.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from types import ModuleType
from typing import List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class UnknownName(KeyError):
    """A name the manifest or a file under ``bench/`` does not define."""


def _check_name(kind: str, name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise UnknownName(f"{kind} name {name!r} is not a valid name")
    return name


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _module(path: str, modname: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """Everything one run of one cell reads, found by name."""

    name: str
    entry: dict            # the manifest's workloads entry
    config: dict           # bench/configs/<name>.json
    traffic: dict          # bench/traffic/<name>.json
    checks: dict           # bench/checks/<cell>.json
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str

    def reference(self) -> ModuleType:
        name = _check_name("reference", self.config["reference"])
        path = os.path.join(self.bench_dir, "references", name + ".py")
        if not os.path.exists(path):
            raise UnknownName(f"no reference {name!r} at {path}")
        return _module(path, f"bench_reference_{name.replace('.', '_')}")


def metric_applies(metric: dict, cell: str, reported: set) -> bool:
    """A metric is read in a cell that its ``workloads`` lists; without
    that key, in every cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    moves = metric.get("moves")
    return moves is None or moves in reported


def find_cell(name: str, manifest: Optional[dict] = None,
              root: str = ROOT) -> Cell:
    manifest = manifest if manifest is not None else load_manifest(root)
    _check_name("workload", name)
    entries = [w for w in manifest["workloads"] if w["name"] == name]
    if not entries:
        raise UnknownName(f"unknown workload {name!r}; known: "
                          f"{[w['name'] for w in manifest['workloads']]}")
    entry = entries[0]
    cfgs = [c for c in manifest["configs"] if c["name"] == entry["config"]]
    if not cfgs:
        raise UnknownName(f"workload {name!r} names unknown config "
                          f"{entry['config']!r}")
    bench_dir = os.path.join(root, "bench")
    config = _load_json(os.path.join(root, cfgs[0]["file"]))
    if config.get("name") != entry["config"]:
        raise UnknownName(f"{cfgs[0]['file']} holds config "
                          f"{config.get('name')!r}, not {entry['config']!r}")
    traffic = load_traffic(entry["traffic"], bench_dir)
    checks_path = os.path.join(bench_dir, "checks", name + ".json")
    if not os.path.exists(checks_path):
        raise UnknownName(f"no correctness limits for {name!r} at "
                          f"{checks_path}")
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in manifest["per_layer"]
                 if metric_applies(m, name, reported)]
    return Cell(name=name, entry=entry, config=config, traffic=traffic,
                checks=_load_json(checks_path), end_to_end=e2e,
                per_layer=per_layer, bench_dir=bench_dir)


def load_traffic(name: str, bench_dir: str = os.path.join(ROOT, "bench")
                 ) -> dict:
    _check_name("traffic", name)
    path = os.path.join(bench_dir, "traffic", name + ".json")
    if not os.path.exists(path):
        raise UnknownName(f"no traffic mix {name!r} at {path}")
    return _load_json(path)


def metric_reader(name: str, bench_dir: str = os.path.join(ROOT, "bench")):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``.

    A quantity split by the end-to-end metric it moves (``<base>.<part>``,
    as ``decode_step_ms.backlog``) is read by ``<base>.py`` unless a file
    of the full name exists."""
    _check_name("metric", name)
    parts = name.split(".")
    for n in range(len(parts), 0, -1):
        stem = ".".join(parts[:n])
        path = os.path.join(bench_dir, "metrics", stem + ".py")
        if os.path.exists(path):
            return _module(path,
                           f"bench_metric_{stem.replace('.', '_')}").read
    raise UnknownName(f"no reader for metric {name!r} under "
                      f"{os.path.join(bench_dir, 'metrics')}")


def peaks(device_kind: str, bench_dir: str = os.path.join(ROOT, "bench")
          ) -> dict:
    """The peak row of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = _load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in table:
        raise UnknownName(f"device kind {device_kind!r} is not in "
                          f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]
