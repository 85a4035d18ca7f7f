"""The benchmark's description, and the files it names.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives it:

  configs/<config>.json    sizes and deployment options of a configuration
  systems/<system>.py      builds the system under test named by a config's
                           "system" key, and its plain-reference check
  limits/<config>.json     the limit of each number `correct` compares
  traffic/<traffic>.json   a traffic mix: clients, moves per request
  readers/<metric>.py      reads one per-layer metric (def read(ctx))
  peaks.json               peak rates of each device kind
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def checked_name(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"{name!r} is not a benchmark name")
    return name


def config_path(name: str) -> Path:
    return HERE / "configs" / f"{checked_name(name)}.json"


def traffic_path(name: str) -> Path:
    return HERE / "traffic" / f"{checked_name(name)}.json"


def limits_path(name: str) -> Path:
    return HERE / "limits" / f"{checked_name(name)}.json"


def reader_path(name: str) -> Path:
    return HERE / "readers" / f"{checked_name(name)}.py"


def system_path(name: str) -> Path:
    return HERE / "systems" / f"{checked_name(name)}.py"


def load_module(path: Path, tag: str):
    """Import one file of the benchmark by its path."""
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{tag}_{path.stem.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell(bench: dict, workload: str) -> dict:
    """The workload entry named `workload`, with its configuration,
    traffic, limits and metrics resolved."""
    found = [w for w in bench["workloads"] if w["name"] == workload]
    if not found:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = found[0]
    config = load_json(config_path(w["config"]))

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "workload": w,
        "config": config,
        "traffic": load_json(traffic_path(w["traffic"])),
        "limits": load_json(limits_path(w["config"])),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def peaks(device_kind: str) -> dict:
    """Peak rates of `device_kind`; a device not in the table is an
    error, never a default."""
    table = load_json(HERE / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"perfbench/peaks.json")
    return table[device_kind]
