"""``readback_idle_ms.<kind>``: the device's idle time inside the
program's ``repro.solve.readback`` spans (``Solver.solve``'s host reads of
its results after the device is done, until it returns) over the number of
those spans in the traced window, in ms: the exact overlap of each span
with the stretches where no op runs, as ``scopes.py`` reads them."""
from stencilbench import scopes


def read(*, reduction, counters, cell):
    seconds = scopes.idle_per_span(reduction, cell, "repro.solve.readback")
    return None if seconds is None else seconds * 1e3
