"""BENCHMARK.json keeps to the benchmark's rules: names, units and keys in
the allowed characters, every cell's files in place, and every cell
reporting set-up, another end-to-end metric and a per-layer metric that
moves one it reports."""
import json
import os
import re

import pytest

from stencilbench.tests.common import ROOT, benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\t\n\r]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
BENCH = benchmark()


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "stencilbench/run.py"]
    assert BENCH["paths"] == ["stencilbench"]
    assert all(PATH.match(p) and ".." not in p for p in BENCH["paths"])
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] \
        + [w["name"] for w in BENCH["workloads"]] \
        + [m["name"] for m in _metrics()]
    names += [w["config"] for w in BENCH["workloads"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    names += [k for c in BENCH["configs"] for k in c["reduced"]]
    assert all(NAME.match(n) for n in names), names
    for kind in ("configs", "workloads"):
        listed = [e["name"] for e in BENCH[kind]]
        assert len(listed) == len(set(listed))
    listed = [m["name"] for m in _metrics()]
    assert len(listed) == len(set(listed))
    assert all(UNIT.match(m["unit"]) for m in _metrics())
    assert all(m["better"] in ("lower", "higher") for m in _metrics())
    texts = [c["source"] for c in BENCH["configs"]] \
        + [e["why"] for e in BENCH["configs"] + BENCH["workloads"]] \
        + [m["layer"] for m in BENCH["per_layer"]] + BENCH["command"]
    assert all(TEXT.match(t) for t in texts)


def test_entry_keys():
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    setup = [m for m in BENCH["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cell_files_and_metrics(cell):
    bench_dir = os.path.join(ROOT, "stencilbench")
    config = {c["name"]: c for c in BENCH["configs"]}[cell["config"]]
    assert config["file"].startswith("stencilbench/")
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    assert {"source", "reduced", "assumed", "deployment"} <= set(cfg)
    assert set(config["reduced"]) == set(cfg["reduced"])
    with open(os.path.join(bench_dir, "traffic", cell["traffic"] + ".json")) \
            as f:
        mix = json.load(f)
    assert os.path.exists(os.path.join(bench_dir, "drivers",
                                       mix["driver"] + ".py"))
    assert os.path.exists(os.path.join(bench_dir, "limits",
                                       cell["name"] + ".json"))

    def applies(m):
        return cell["name"] in m.get("workloads", [cell["name"]])
    e2e = {m["name"] for m in BENCH["end_to_end"] if applies(m)}
    assert "setup_s" in e2e and len(e2e) >= 2
    layers = [m for m in BENCH["per_layer"] if applies(m)]
    assert layers
    for m in layers:
        assert m["moves"] in e2e, m["name"]
        assert os.path.exists(os.path.join(
            bench_dir, "metrics", m["name"].split(".")[0] + ".py"))


def test_config_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_layers_are_named_alike():
    """Each layer is named on one line, one layer is not written two ways,
    and the metrics one reader gives (its name up to the first dot) keep to
    one layer.  Any new layer a later metric names is welcome."""
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(TEXT.match(layer) and layer == layer.strip()
               for layer in layers), layers
    folded = {" ".join(layer.lower().split()) for layer in layers}
    assert len(folded) == len(layers), sorted(layers)
    by_reader = {}
    for m in BENCH["per_layer"]:
        by_reader.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in by_reader.values()), by_reader


def test_four_chip_cells_within_share():
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_check_fits_its_time():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
