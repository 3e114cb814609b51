"""``stencilbench/trace.py`` on a small recorded trace: busy time is the
union of overlapping ops inside the window, the sweep-kernel rule picks
the kernel's events, and idle gaps are put down to the innermost benchmark
span and the innermost other event of its thread."""
import os

import pytest
from jax.profiler import ProfileData

from stencilbench import trace
from stencilbench.metrics import (idle_share, kernel_busy_share,
                                  kernel_gpts_per_s)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "small_tpu.xplane.pbtxt")


@pytest.fixture(scope="module")
def reduction():
    with open(DATA) as f:
        profile = ProfileData.from_text_proto(f.read())
    return trace.reduce(trace.from_profile(profile))


def test_busy_union_and_window(reduction):
    assert reduction.chips == 1
    assert reduction.window_s == pytest.approx(20000e-9)
    assert reduction.busy_s == pytest.approx(13000e-9)
    assert reduction.idle_share == pytest.approx(1 - 13000 / 20000)


def _op(reduction, prefix):
    return sum(s for n, s in reduction.op_seconds.items()
               if n.startswith(prefix))


def test_op_seconds_are_clipped_to_the_window(reduction):
    assert _op(reduction, "%jacobi2d_fused_step.3 =") == \
        pytest.approx(10500e-9)
    assert _op(reduction, "%copy.3 =") == pytest.approx(2000e-9)
    assert _op(reduction, "%multiply_add_fusion.7 =") == \
        pytest.approx(1500e-9)
    assert _op(reduction, "%while.3") == 0  # a container, not an op
    assert _op(reduction, "jit_run_fixed") == 0  # not on the op line


def test_kernel_rule_and_readers(reduction):
    names = {n.split(" = ")[0]: n for n in reduction.op_seconds}
    assert kernel_gpts_per_s.is_sweep_kernel(names["%jacobi2d_fused_step.3"])
    assert not kernel_gpts_per_s.is_sweep_kernel(
        names["%multiply_add_fusion.7"])
    assert reduction.seconds_of(kernel_gpts_per_s.is_sweep_kernel) == \
        pytest.approx(10500e-9)
    args = dict(reduction=reduction, counters={"point_sweeps": 21000},
                cell={})
    assert kernel_gpts_per_s.read(**args) == pytest.approx(
        21000 / 10500e-9 / 1e9)
    assert kernel_busy_share.read(**args) == pytest.approx(
        100 * 10500 / 13000)
    assert idle_share.read(**args) == pytest.approx(100 * 7000 / 20000)


def test_idle_gaps_attributed_to_spans(reduction):
    python = "$fixed.py:60 window"
    got = sorted((span, round(s * 1e9)) for span, s in reduction.gaps)
    assert got == sorted([
        (f"{trace.NO_SPAN} > {python}", 500),
        (f"bench.solve > {python}", 500),
        ("bench.solve > BlockHostUntilReady", 2000),
        (f"{trace.NO_SPAN} > {python}", 4000)])
    b = reduction.breakdown()
    assert b["device_ops"][0] == [
        "%jacobi2d_fused_step.3 custom-call f32[8,64,64]",
        pytest.approx(10500e-9)]
    assert [g[1] for g in b["idle_gaps"]] == [pytest.approx(4500e-9),
                                              pytest.approx(2000e-9),
                                              pytest.approx(500e-9)]
    assert b["idle_gaps"][0][0].startswith(
        f"{trace.NO_SPAN} > {python} (2 gaps")
    assert b["idle_gaps"][1][0].startswith(
        "bench.solve > BlockHostUntilReady (1 gaps")


def test_no_device_plane_reduces_to_nothing():
    assert trace.reduce(trace.Trace(ops={}, spans=[
        ("bench.window", 0.0, 10.0)])) is None
