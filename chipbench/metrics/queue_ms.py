"""Engine admission (``ServingEngine._prime_slot``): the engine's own
``queue_ms`` over its ``primes`` across the window, the mean time a request
waited in the engine's queue, from joining it to the start of its prime."""


def read(run):
    n = run.delta("primes")
    return run.delta("queue_ms") / n if n else None
