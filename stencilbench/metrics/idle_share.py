"""``idle_share.<kind>``: the share of the traced window in which no
operation ran on the device, in per cent (``trace.Reduction.idle_share``)."""


def read(*, reduction, counters, cell):
    return None if reduction is None else 100.0 * reduction.idle_share
