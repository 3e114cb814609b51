"""The served cell's driver, rehearsed through a BENCHMARK.json that holds
the cell (``BENCHMARK.json`` leaves it out while the program's ``conv``
bucket backend gives wrong answers on the chip; see PERF.md): correct on
its own, not correct under the control or any planted fault."""
import json
import os

import pytest

from stencilbench import run as bench
from stencilbench.tests.common import FAULTS, ROOT, benchmark, plant, \
    rehearse

CELL = {"name": "table1-serve", "config": "table1-laplace2d",
        "traffic": "serve-closed-32", "chips": 1,
        "why": "32 closed-loop callers, 64x64/60x60 grids to rtol 1e-5"}
METRICS = [
    {"name": "serve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.05,
     "source": "host_clock", "workloads": ["table1-serve"]},
    {"name": "serve_solves_per_s", "unit": "solves/s", "better": "higher",
     "bound": 0.05, "source": "host_clock", "workloads": ["table1-serve"]}]
LAYERS = [
    {"name": "mean_batch.serve", "unit": "requests", "better": "higher",
     "source": "program_counter", "layer": "serving engine",
     "moves": "serve_solves_per_s", "workloads": ["table1-serve"]},
    {"name": "window_misses.serve", "unit": "misses", "better": "lower",
     "source": "program_counter", "layer": "plan cache",
     "moves": "serve_p95_ms", "workloads": ["table1-serve"]}]


@pytest.fixture
def served(tmp_path, monkeypatch):
    bench_json = benchmark()
    bench_json["workloads"].append(CELL)
    bench_json["end_to_end"] += METRICS
    bench_json["per_layer"] += LAYERS
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench_json))
    for d in ("stencilbench", "src"):
        os.symlink(os.path.join(ROOT, d), tmp_path / d)
    monkeypatch.setattr(bench, "ROOT", str(tmp_path))
    monkeypatch.setattr(bench, "trace_dir",
                        lambda cell: str(tmp_path / "trace" / cell))


def test_served_rehearsal(served, capsys):
    line = rehearse(capsys, CELL["name"])
    assert line["correct"] is True
    assert set(line["metrics"]) == {"serve_p95_ms", "serve_solves_per_s",
                                    "setup_s"}
    line = rehearse(capsys, CELL["name"], trace=1)
    assert line["correct"] is True
    assert line["metrics"]["window_misses.serve"]["value"] == 0
    assert line["metrics"]["mean_batch.serve"]["value"] >= 1


@pytest.mark.parametrize("fault", ["bf16"] + FAULTS)
def test_served_fault_is_not_correct(served, fault, capsys, monkeypatch):
    if fault != "bf16":
        plant(monkeypatch, fault)
    line = rehearse(capsys, CELL["name"], control=fault == "bf16")
    assert line["correct"] is False, line["checks"]
