"""BENCHMARK.json against its contract, and every file it names found
by name: configuration, traffic, limits, system and reader."""

import json
import math
import re

import pytest

from perfbench import spec

BENCH = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|experts_per_token|channels")
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(BENCH)) <= 64 * 1024
    assert 1 <= len(BENCH["command"]) <= 32
    assert all(LINE.match(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.match(r"^[A-Za-z0-9_./-]{1,200}$", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    total = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert total <= 43200


def test_unique_names():
    for group in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("c", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and LINE.match(c["source"])
    assert LINE.match(c["why"])
    assert c["file"] == f"perfbench/configs/{c['name']}.json"
    assert len(c["reduced"]) <= 16
    for key in c["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key)
    cfg = spec.load_json(spec.config_path(c["name"]))
    assert cfg["reduced"] == c["reduced"]
    assert spec.system_path(cfg["system"]).is_file()
    assert spec.limits_path(c["name"]).is_file()
    assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_resolves(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for k in ("name", "config", "traffic"):
        assert NAME.match(w[k])
    assert w["chips"] in (1, 4) and LINE.match(w["why"])
    cell = spec.cell(BENCH, w["name"])
    assert spec.traffic_path(w["traffic"]).is_file()
    e2e = {m["name"] for m in cell["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell["per_layer"]
    for m in cell["per_layer"]:
        assert spec.reader_path(m["name"]).is_file()
        assert m["moves"] in e2e
    pairs = [(x["config"], x["traffic"]) for x in BENCH["workloads"]]
    assert pairs.count((w["config"], w["traffic"])) == 1


def test_four_chip_cells():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    for cell in m.get("workloads", []):
        assert cell in CELLS
    if m in BENCH["end_to_end"]:
        allowed = {"name", "unit", "better", "bound", "source", "workloads"}
        assert set(m) <= allowed and m["source"] in ("host_clock",
                                                     "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and math.isfinite(m["bound"])
    else:
        allowed = {"name", "unit", "better", "source", "layer", "moves",
                   "workloads"}
        assert set(m) <= allowed
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert LINE.match(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


def test_layers_named_alike():
    by_prefix = {}
    for m in BENCH["per_layer"]:
        by_prefix.setdefault(m["layer"].split(" ")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_prefix.values())


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["flops_per_s"] == 197e12
    assert p["source"]
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


def test_names_are_checked():
    with pytest.raises(ValueError):
        spec.config_path("../etc")
