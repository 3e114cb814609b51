"""``kernel_busy_share.<kind>``: the sweep kernels' device time over the
device's busy time in the traced window, in per cent.  The rest is what
the device does besides sweeping: relayout copies, residual reductions,
loop control.  The kernels are matched by ``kernel_gpts_per_s``'s rule."""
from stencilbench.metrics.kernel_gpts_per_s import is_sweep_kernel


def read(*, reduction, counters, cell):
    if reduction is None or not reduction.busy_s:
        return None
    seconds = reduction.seconds_of(is_sweep_kernel)
    return 100.0 * seconds / reduction.busy_s if seconds else None
