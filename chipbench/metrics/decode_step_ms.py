"""Engine decode loop (``ServingEngine.step``): the engine's own
``decode_ms`` over ``decode_steps`` across the window — dispatch to the
logits being ready, on the host clock."""


def read(run):
    n = run.delta("decode_steps")
    return run.delta("decode_ms") / n if n else None
