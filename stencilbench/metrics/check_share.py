"""``check_share.<kind>``: device time of the solver's convergence check
over the device's busy time in the traced window, in per cent.  The check
is the ops under the program's ``repro.check`` scope
(``core/solver.py``'s loop body after the sweeps: residual norms, the
convergence test, freezing converged instances, the history write), as
``scopes.py`` reads them from each op's HLO ``op_name``."""
from stencilbench import scopes


def read(*, reduction, counters, cell):
    return scopes.busy_share(reduction, cell, "repro.check")
