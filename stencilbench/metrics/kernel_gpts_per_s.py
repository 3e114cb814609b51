"""``kernel_gpts_per_s.<kind>``: point-sweeps of the calls in the traced
window (``data.point_sweeps``, the problem's count whatever implements the
sweep) over the summed device time of the sweep kernels, in Gpts/s.

The sweep kernels are the Pallas calls of ``kernels/jacobi_fused.py`` and
``kernels/stencil3d.py``.  In the trace of a TPU v5e each call is an event
of the ``XLA Ops`` line named by its HLO instruction, a Mosaic custom call:
``%jacobi2d_fused_step.3 = f32[131072,64,64]{...} custom-call(...),
custom_call_target="tpu_custom_call", ...``.  The cells run no other Mosaic
kernel, so :func:`is_sweep_kernel` takes every ``tpu_custom_call``.
"""
SWEEP_KERNEL = 'custom_call_target="tpu_custom_call"'


def is_sweep_kernel(name: str) -> bool:
    return SWEEP_KERNEL in name


def read(*, reduction, counters, cell):
    if reduction is None or not counters.get("point_sweeps"):
        return None
    seconds = reduction.seconds_of(is_sweep_kernel)
    return counters["point_sweeps"] / seconds / 1e9 if seconds else None
