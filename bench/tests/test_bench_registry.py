"""The registry finds configurations, traffic, limits and metric readers
by the names BENCHMARK.json gives, refuses unknown names, and the peak
table refuses an unknown device kind.  The manifest keeps the
benchmark's contract on names, units and keys."""
import json
import os
import re

import pytest

from bench import registry

MANIFEST = registry.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_every_cell_resolves(cell):
    c = registry.find_cell(cell)
    assert c.config["name"] == c.entry["config"]
    assert c.traffic["kind"] in ("open_poisson", "backlog", "closed")
    assert c.reference().forward
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert m["moves"] in names
    for key in ("max_logit_gap", "min_checked_requests"):
        assert key in c.checks["limits"]


def test_every_metric_has_a_reader():
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert callable(registry.metric_reader(m["name"]))


@pytest.mark.parametrize("kind,name", [
    ("workload", "no-such-cell"), ("traffic", "no-such-mix"),
    ("metric", "no_such_metric"), ("workload", "../etc"),
    ("metric", "a/b"), ("metric", "backlog.decode_step_ms")])
def test_unknown_names_refused(kind, name):
    fn = {"workload": registry.find_cell, "traffic": registry.load_traffic,
          "metric": registry.metric_reader}[kind]
    with pytest.raises(registry.UnknownName):
        fn(name)


def test_peak_table():
    row = registry.peaks("TPU v5 lite")
    assert row["bf16_flops_per_s"] == 197e12
    assert row["hbm_bytes_per_s"] == 819e9
    with pytest.raises(registry.UnknownName):
        registry.peaks("TPU v4")
    with pytest.raises(registry.UnknownName):
        registry.peaks("cpu")


def test_manifest_keeps_the_contract():
    m = MANIFEST
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["bench"] and m["command"][1] == "bench/run.py"
    assert 1 <= m["run_seconds"] <= 51
    seen = set()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        spec = json.load(open(os.path.join(registry.ROOT, c["file"])))
        assert sorted(spec.get("reduced", [])) == sorted(c["reduced"])
        seen.add(c["name"])
    used = set()
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        used.add(w["config"])
    assert used == seen
    for e in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher")
    for e in m["end_to_end"]:
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in m["per_layer"]:
        assert e["name"].endswith("_roofline") <= (e["unit"] == "%")
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
