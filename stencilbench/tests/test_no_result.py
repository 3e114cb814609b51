"""Where a run cannot measure the chip it gives no result: without a TPU,
without the program beside it, or on a TPU asked to rehearse."""
import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from stencilbench import run as bench
from stencilbench.tests.common import ROOT

ARGS = ["--workload", "table1-tiles-fixed", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "stencilbench", "run.py"),
         *ARGS, *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_no_tpu_exits_without_a_result():
    if jax.devices()[0].platform == "tpu":
        pytest.skip("this process has a TPU")
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("extra", [[], ["--rehearse"]],
                         ids=["run", "rehearse"])
def test_benchmark_files_alone_give_no_result(tmp_path, extra):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "stencilbench"),
                    tmp_path / "stencilbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), *extra)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "cannot import the program" in p.stderr


def test_rehearsal_is_refused_on_a_tpu(monkeypatch, capsys):
    dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [dev])
    assert bench.main(ARGS + ["--rehearse"]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "off the chip only" in err
