"""Every cell, rehearsed at tiny sizes on the CPU, prints the contract's
last line with ``correct`` true, and its traced run the per-layer metrics
that a CPU can give (the program's counters; no device-trace metric)."""
import math

import pytest

from stencilbench import run as bench
from stencilbench.tests.common import benchmark, rehearse

CELLS = [w["name"] for w in benchmark()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_is_correct(cell, capsys):
    line = rehearse(capsys, cell)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    bench = benchmark()
    want = {m["name"] for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and math.isfinite(m["value"]), name
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_traced_rehearsal(cell, capsys, tmp_path, monkeypatch):
    monkeypatch.setattr(bench, "trace_dir", lambda c: str(tmp_path / c))
    line = rehearse(capsys, cell, trace=1)
    assert line["correct"] is True
    layers = {m["name"]: m for m in benchmark()["per_layer"]
              if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) <= set(layers)
    # a CPU run has no TPU plane: nothing read from the device trace
    assert not any(layers[n]["source"] == "device_trace"
                   for n in line["metrics"])
    counted = {n for n, m in layers.items()
               if m["source"] == "program_counter"}
    assert counted <= set(line["metrics"])
