"""Engine decode loop (``ServingEngine.step``): the live rows over the rows
of resident state that the decode steps carried across the window, the
engine's own ``decode_rows`` over its ``state_rows``.  On the
slot-granular path every decode step reads and writes the recurrent state
of all ``batch_size`` rows, live or not."""


def read(run):
    n = run.delta("state_rows")
    return 100.0 * run.delta("decode_rows") / n if n else None
