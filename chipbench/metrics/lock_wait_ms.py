"""Engine admission (``ServingEngine.submit``): the engine's own
``lock_wait_ms`` over its ``submits`` across the window, the mean time a
``submit`` waited for the engine's lock (the ``engine.submit.lock`` span).
``step`` holds the lock through each admission prefill and decode step."""


def read(run):
    n = run.delta("submits")
    return run.delta("lock_wait_ms") / n if n else None
