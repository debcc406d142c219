"""``BENCHMARK.json`` and the files it names hold together.

Every entry is discovered from the file itself, so a cell, configuration or
metric that a later change adds is checked by the same tests."""
import json
import math
import re

import pytest

from chipbench import spec

BENCH = spec.load_benchmark()
NAME = spec.NAME
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert (spec.ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32
    for word in cmd:
        assert not word.startswith("/") and ".." not in word
        if word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
            <= 43200)
    assert len((spec.ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_are_unique_and_well_formed():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(names) == len(set(names)), group
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for n in names + CELLS + [c["name"] for c in BENCH["configs"]]:
        assert NAME.fullmatch(n), n


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert any(entry["file"].startswith(p + "/") for p in BENCH["paths"])
    config = json.loads((spec.ROOT / entry["file"]).read_text())
    assert config["name"] == entry["name"]
    family = spec.load_family(config["family"])
    assert family.dims_of(config).vocab == config["vocab_size"]
    assert config["source"] == entry["source"]
    assert entry["source"].startswith("https://")
    assert len(entry["reduced"]) <= 16
    widths = re.compile(r"(hidden_size|intermediate|latent|state|_dim$|"
                        r"_rank$|expan|experts_per_tok)")
    for key in entry["reduced"]:
        assert NAME.fullmatch(key)
        assert not widths.search(key), f"{key} is a width"
        assert key in config["published"], key
        assert config[key] != config["published"][key], key
    assert 1 <= len(entry["why"]) <= 200
    assert entry["name"] in {w["config"] for w in BENCH["workloads"]}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    loaded = spec.load_cell(cell["name"])
    assert loaded.traffic["dp"] in (1, cell["chips"])
    assert set(loaded.traffic["kernels"]) >= {"collage_update"}
    for k in ("grad_gap", "change_gap"):
        assert 0 < loaded.limits[k] < math.inf, k
    # every cell reports setup_s, another end-to-end metric, and a
    # per-layer metric
    e2e = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert loaded.per_layer
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert pairs.count((cell["config"], cell["traffic"])) == 1


def test_four_chip_cells_are_few():
    four = sum(1 for w in BENCH["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BENCH["workloads"]) // 2)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.fullmatch(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in spec.SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS


UNIT = spec.UNIT


@pytest.mark.parametrize("metric", BENCH["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    allowed = {"name", "unit", "better", "bound", "source", "workloads"}
    assert set(metric) <= allowed
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25


def test_setup_s_is_declared():
    (m,) = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert m["unit"] == "s" and m["better"] == "lower"


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    allowed = {"name", "unit", "better", "source", "layer", "moves",
               "workloads"}
    assert set(metric) <= allowed
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    moved = [m for m in BENCH["end_to_end"] if m["name"] == metric["moves"]]
    assert moved, f"{metric['name']} moves no end-to-end metric"
    for cell in metric.get("workloads", CELLS):
        assert spec.reports(moved[0], cell), (metric["name"], cell)
    assert callable(spec.metric_reader(metric["name"]))
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"


def test_one_layer_name_per_layer():
    # metrics of one layer give the same name, letter for letter
    names = {m["layer"] for m in BENCH["per_layer"]}
    folded = {n.lower().strip() for n in names}
    assert len(folded) == len(names)


def test_peaks_are_known_for_the_chip():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v9 imaginary")
