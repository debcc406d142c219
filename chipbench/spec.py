"""What ``BENCHMARK.json`` declares, and the files it names.

A cell ``<config>.<traffic>`` is found by name: its configuration is
``chipbench/configs/<config>.json`` (the file the ``configs`` entry names),
its traffic mix ``chipbench/traffic/<traffic>.json``, the limits of its
output check ``chipbench/limits/<cell>.json``, and each per-layer metric
``chipbench/metrics/<metric>.py``. Adding a cell, a configuration or a
metric adds files and entries; no code here changes."""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: tuple      # metric entries this cell reports with --trace 0
    per_layer: tuple       # metric entries this cell reports with --trace 1


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[w["config"]]["file"])
    traffic = _json(HERE / "traffic" / f"{w['traffic']}.json")
    limits = _json(HERE / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=tuple(m for m in bench["end_to_end"]
                                 if reports(m, name)),
                per_layer=tuple(m for m in bench["per_layer"]
                                if reports(m, name)))


def metric_reader(name: str):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.metrics.{name}", path)
    if spec is None or not path.exists():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"chipbench/peaks.json")
    return table[device_kind]
