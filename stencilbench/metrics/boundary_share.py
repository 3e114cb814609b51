"""``boundary_share.<kind>``: device time of pinning the Dirichlet shell
before each chunk of sweeps over the device's busy time in the traced
window, in per cent: the ops under the program's ``repro.boundary`` scope
(``kernels/ops.py``), as ``scopes.py`` reads them from each op's HLO
``op_name``."""
from stencilbench import scopes


def read(*, reduction, counters, cell):
    return scopes.busy_share(reduction, cell, "repro.boundary")
