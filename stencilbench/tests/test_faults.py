"""The comparison that decides ``correct`` fails when the timed path is
wrong.  Each run skips the look for a chip (``--rehearse``) and drives the
rest of a run, once with the control (``--control``: the program's own
bfloat16 path in place of the float32 the configuration states) and once
with each fault of ``common.plant`` underneath the driver: a solve that
hands back its input, half of every batch left unsolved, one point of
every answer altered.

The cells run on one chip, so no exchange between chips can be left out.
"""
import pytest

from stencilbench.tests.common import FAULTS, benchmark, plant, rehearse

CELLS = [w["name"] for w in benchmark()["workloads"]]


@pytest.mark.parametrize("fault", ["bf16"] + FAULTS)
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault, capsys, monkeypatch):
    if fault != "bf16":
        plant(monkeypatch, fault)
    line = rehearse(capsys, cell, control=fault == "bf16")
    assert line["correct"] is False, line["checks"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
