"""``stencilbench/scopes.py`` and its readers on a small hand-made trace in
the shape a TPU v5e run writes: ops are put in the program scope their
``tf_op`` metadata names, shares are taken of the busy union, and the idle
time inside the readback spans is their exact overlap with the gaps.  The
numbers are worked out in the fixture's header."""
import importlib
import os

import pytest
from jax.profiler import ProfileData

from stencilbench import run as bench
from stencilbench import scopes, trace
from stencilbench.metrics import (boundary_share, check_share,
                                  readback_idle_ms)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "scoped_tpu.xplane.pbtxt")
CELL = {"name": "table1-tiles-converge"}
READERS = [check_share, boundary_share, readback_idle_ms]


def _text():
    with open(DATA) as f:
        return f.read()


def _serialized(text):
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture(scope="module")
def decoded():
    return scopes.decode(_serialized(_text()))


def _traced(monkeypatch, tmp_path, text):
    """Put ``text`` where ``run.py`` leaves the cell's trace; return the
    reduction ``run.py`` would hand the readers."""
    cell_dir = tmp_path / CELL["name"] / "plugins" / "profile" / "1"
    cell_dir.mkdir(parents=True)
    path = cell_dir / "host.xplane.pb"
    path.write_bytes(_serialized(text))
    monkeypatch.setattr(bench, "trace_dir", lambda c: str(tmp_path / c))
    return trace.reduce(trace.load(str(path)))


def test_scopes_of_ops(decoded):
    assert decoded.window == (1000, 21000)
    names = {name.split(" = ")[0]: scope
             for name, scope, _, _ in decoded.ops[0]}
    assert names == {
        "%copy.1": scopes.UNSCOPED,
        "%multiply_add_fusion.2": "repro.boundary",
        "%jacobi2d_fused_step.4": "repro.sweep",
        "%multiply_reduce_fusion.3": "repro.check",
        "%dynamic-update-slice.4": "repro.check"}   # no while: a container


def test_scope_path_parsing():
    assert scopes.scope_of("jit(loop)/while/body/repro.check/sub:") == \
        "repro.check"
    assert scopes.scope_of("a/repro.sweep/b/repro.boundary/add:") == \
        "repro.boundary"
    assert scopes.scope_of("jit(loop)/while:") == scopes.UNSCOPED
    assert scopes.scope_of("") == scopes.UNSCOPED


def test_seconds_by_scope(decoded):
    by_scope, unscoped = decoded.seconds()
    assert by_scope == pytest.approx({
        "repro.check": 2700e-9, "repro.boundary": 900e-9,
        "repro.sweep": 7000e-9, scopes.UNSCOPED: 1000e-9})
    assert unscoped == pytest.approx(
        {"%copy.1 copy f32[8,64,64]": 1000e-9})


def test_times_agree_with_trace_reduction(decoded):
    """The hand decoder reads the op times ``jax.profiler`` reads."""
    reduction = trace.reduce(trace.from_profile(
        ProfileData.from_text_proto(_text())))
    assert reduction.busy_s == pytest.approx(11100e-9)
    assert sum(decoded.seconds()[0].values()) == pytest.approx(
        sum(reduction.op_seconds.values()))


def test_solve_spans(decoded):
    spans = sorted((solve, name, s, e) for name, solve, s, e in decoded.spans)
    assert spans == [
        (0, "repro.solve.dispatch", 2100, 2600),
        (0, "repro.solve.readback", 8000, 9900),
        (0, "repro.solve.wait", 2600, 8000),
        (1, "repro.solve.dispatch", 11100, 11500),
        (1, "repro.solve.readback", 16500, 19800),
        (1, "repro.solve.wait", 11500, 16500)]


def test_idle_is_the_exact_overlap(decoded):
    assert decoded.idle_in("repro.solve.readback") == [
        (0, pytest.approx(1900e-9)), (1, pytest.approx(2900e-9))]


def test_readers(monkeypatch, tmp_path):
    reduction = _traced(monkeypatch, tmp_path, _text())
    args = dict(reduction=reduction, counters={}, cell=CELL)
    assert check_share.read(**args) == pytest.approx(100 * 2700 / 11100)
    assert boundary_share.read(**args) == pytest.approx(100 * 900 / 11100)
    assert readback_idle_ms.read(**args) == pytest.approx(2400e-6)


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_no_tpu_plane_reads_nothing(reader, monkeypatch, tmp_path):
    """A rehearsal on the CPU: ``run.py`` hands no reduction, and the trace
    it leaves has no TPU plane."""
    text = _text().replace('name: "/device:TPU:0"', 'name: "/host:CPU:9"')
    _traced(monkeypatch, tmp_path, text)
    assert reader.read(reduction=None, counters={}, cell=CELL) is None
    assert scopes.of_cell(CELL) is None


@pytest.mark.parametrize("reader", READERS, ids=lambda r: r.__name__)
def test_a_program_without_scopes_reads_nothing(reader, monkeypatch,
                                                tmp_path):
    """A program that names no scope and no span (the solver before they
    were added) gives no reading, and no error."""
    reduction = _traced(monkeypatch, tmp_path,
                        _text().replace("repro.", "other."))
    assert reduction is not None
    assert reader.read(reduction=reduction, counters={}, cell=CELL) is None


@pytest.mark.parametrize("named", [True, False], ids=["change", "parent"])
def test_scopes_script(named, tmp_path, capsys):
    """The operator's view of one trace: seconds by scope, spans by solve,
    and the shared-clock check (the fixture's second check reduce runs
    400 ns into readback 1, which starts 3300 ns before its end)."""
    script = importlib.import_module("stencilbench.scripts.scopes")
    text = _text() if named else _text().replace("repro.", "other.")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_serialized(text))
    assert script.main(str(path)) == 0
    out = capsys.readouterr().out
    if not named:
        assert "(no scope)" in out and "repro.check" not in out
        assert out.rstrip().endswith("no repro.solve.* span in the window")
        return
    assert "  repro.check          0.000003 s   24.324% of busy" in out
    assert "      0.000001 s  %copy.1 copy f32[8,64,64]" in out
    assert "      1      0.000      0.005      0.003      0.003" in out
    assert out.rstrip().endswith(
        "clock: 0 of 2 sweep-kernel ops outside every solve's dispatch..wait,"
        " by at most 0.000 ms; 1 ops inside a readback span, from at most"
        " 0.003 ms before its end")
