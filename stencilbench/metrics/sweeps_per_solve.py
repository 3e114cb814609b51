"""``sweeps_per_solve.<kind>``: the sweeps the last solve of the window ran,
the most of any instance in its batch (the loop runs until the last
instance converges), as ``SolveResult.iterations`` reports them."""


def read(*, reduction, counters, cell):
    return counters.get("sweeps_per_solve")
