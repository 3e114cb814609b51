"""A configuration, a traffic mix and a per-layer metric of a layer not yet
named, added as files of their own with entries in BENCHMARK.json, are
found by name: the run uses them, and the benchmark's own tests pass on
them, without an edit to any file the benchmark already has."""
import filecmp
import json
import os
import shutil
import subprocess
import sys

from stencilbench.tests.common import ROOT

CONFIG = {
    "name": "tiny-laplace2d", "source": "a test's own problem",
    "operator": "laplace_jacobi", "ndim": 2, "tile": [12, 20], "tiles": 6,
    "dtype": "float32", "bc": 0.5, "reduced": {}, "assumed": {},
    "deployment": "none: a test of the harness"}
MIX = {"driver": "fixed", "why": "calls of 7 sweeps", "sweeps_per_call": 7,
       "backend": "auto", "sample": 3}
READER = '''"""calls_seen.<kind>: calls of the traced window."""


def read(*, reduction, counters, cell):
    return counters.get("calls")
'''


def _env():
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return env


def _run(root, trace):
    env = _env()
    p = subprocess.run(
        [sys.executable, "stencilbench/run.py", "--workload", "tiny-fixed",
         "--seed", "2147483900", "--seconds", "0.5", "--trace", str(trace),
         "--rehearse"], cwd=root, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_new_files_are_found_by_name(tmp_path):
    bench_dir = tmp_path / "stencilbench"
    shutil.copytree(os.path.join(ROOT, "stencilbench"), bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    (bench_dir / "configs" / "tiny-laplace2d.json").write_text(
        json.dumps(CONFIG))
    (bench_dir / "traffic" / "fixed-7.json").write_text(json.dumps(MIX))
    (bench_dir / "metrics" / "calls_seen.py").write_text(READER)
    (bench_dir / "limits" / "tiny-fixed.json").write_text(
        json.dumps({"max_abs_err": {"limit": 1e-4}}))

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-laplace2d", "source": "a test",
        "file": "stencilbench/configs/tiny-laplace2d.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({"name": "tiny-fixed",
                               "config": "tiny-laplace2d",
                               "traffic": "fixed-7", "chips": 1,
                               "why": "a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "gpts_per_s":
            m["workloads"].append("tiny-fixed")
    bench["per_layer"].append({
        "name": "calls_seen.fixed", "unit": "calls", "better": "higher",
        "source": "program_counter", "layer": "harness under test",
        "moves": "gpts_per_s", "workloads": ["tiny-fixed"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    # the rules on BENCHMARK.json hold for the copy as they stand
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist",
         "stencilbench/tests/test_benchmark_json.py"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert p.returncode == 0, p.stdout[-3000:]

    line = _run(tmp_path, trace=0)
    assert line["correct"] is True
    assert set(line["metrics"]) == {"gpts_per_s", "setup_s"}
    line = _run(tmp_path, trace=1)
    assert line["correct"] is True
    assert line["metrics"]["calls_seen.fixed"]["value"] == line["attempted"]

    # no file the benchmark had was touched
    cmp = filecmp.dircmp(os.path.join(ROOT, "stencilbench"), bench_dir,
                         ignore=["__pycache__"])

    def changed(d):
        return d.diff_files + [f for sub in d.subdirs.values()
                               for f in changed(sub)]
    assert changed(cmp) == []
