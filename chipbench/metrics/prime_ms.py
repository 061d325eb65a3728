"""Engine admission (``ServingEngine._prime_slot``): the engine's own
``prefill_ms`` over the window, per admission prefill dispatched in it."""


def read(run):
    n = sum(1 for c in run.calls if c.kind == "prime")
    return run.delta("prefill_ms") / n if n else None
