"""Engine decode loop (``ServingEngine.step``): the engine's own
``host_ms`` over ``decode_steps`` across the window, the host time per
decode step, which is each ``engine.step`` span less its primes and its
decode (admission bookkeeping, inputs and page tables, argmax and copy to
the host, emitting and finishing rows)."""


def read(run):
    n = run.delta("decode_steps")
    if "host_ms" not in run.engine1 or not n:
        return None
    return run.delta("host_ms") / n
