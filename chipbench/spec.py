"""What ``BENCHMARK.json`` declares, and the files it names.

A cell ``<config>.<traffic>`` is found by name: its configuration is
``chipbench/configs/<config>.json`` (the file the ``configs`` entry names),
its model family ``chipbench/families/<family>.py`` (the configuration's
``"family"``), its traffic mix ``chipbench/traffic/<traffic>.json``, the
limits of its output check ``chipbench/limits/<cell>.json``, and each
per-layer metric ``chipbench/metrics/<metric>.py``. Adding a cell, a
configuration, a model family or a metric adds files and entries; no code
here changes.

A family file holds what the harness must know of one architecture
(``FAMILY_API``): ``dims_of(config)``, its sizes, hashable, with ``vocab``
the ids the traffic draws; ``shapes(dims)``, the named tensors (name →
(shape, standard deviation)); ``to_program(w)`` and ``from_program(p)``
between those tensors and the program's parameter tree;
``check_widths(cfg, opt, dims, traffic)``, what differs between the
program's config and optimizer and the files; ``SMOKE``, the widths of the
CPU tests; ``train_flops_per_token(dims, seq_len)``; ``flash_widths(dims)``
(``counts.flash.Widths``); and ``run``, its plain reference's readings of
the set-up steps. A configuration's ``"launcher"`` list, if any, is
appended to the launcher's arguments after the traffic's."""
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
FAMILY_API = ("dims_of", "shapes", "to_program", "from_program",
              "check_widths", "SMOKE", "train_flops_per_token",
              "flash_widths", "run")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    family: object         # the module chipbench/families/<family>.py
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


def _load(path: pathlib.Path, module: str):
    spec = importlib.util.spec_from_file_location(module, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_family(name: str, directory: pathlib.Path = HERE / "families"):
    """The module ``<directory>/<name>.py`` of a model family."""
    path = directory / f"{name}.py"
    if not NAME.fullmatch(name) or not path.is_file():
        raise SpecError(f"no family file {path} for family {name!r}")
    mod = _load(path, f"chipbench.families.{name}")
    missing = [k for k in FAMILY_API if not hasattr(mod, k)]
    if missing:
        raise SpecError(f"family file {path} lacks {', '.join(missing)}")
    return mod


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """A cell of ``<root>/BENCHMARK.json`` with the files it names under
    ``<root>/chipbench``."""
    here = root / HERE.name
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config_path = root / configs[w["config"]]["file"]
    config = _json(config_path)
    if "family" not in config:
        raise SpecError(f"{config_path} names no family")
    family = load_family(config["family"], here / "families")
    traffic = _json(here / "traffic" / f"{w['traffic']}.json")
    limits = _json(here / "limits" / f"{name}.json")
    return Cell(name=name, chips=int(w["chips"]), config=config,
                family=family, traffic=traffic, limits=limits,
                end_to_end=tuple(m for m in bench["end_to_end"]
                                 if reports(m, name)),
                per_layer=tuple(m for m in bench["per_layer"]
                                if reports(m, name)))


def metric_reader(name: str):
    """The ``read`` function of ``chipbench/metrics/<name>.py``."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists():
        raise SpecError(f"no reader {path} for per-layer metric {name!r}")
    return _load(path, f"chipbench.metrics.{name}").read


def peaks(device_kind: str) -> dict:
    table = _json(HERE / "peaks.json")["devices"]
    if device_kind not in table:
        raise SpecError(f"no peaks for device kind {device_kind!r} in "
                        f"chipbench/peaks.json")
    return table[device_kind]
