"""``mean_batch.<kind>``: requests answered per batched dispatch over the
window, from the change in the serving engine's ``EngineStats``
(``completed`` over ``batches``)."""


def read(*, reduction, counters, cell):
    return counters.get("mean_batch")
