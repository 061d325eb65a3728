"""Engine admission (``ServingEngine.submit``): the median time a
request's ``submit`` call took in the window, timed around the call.  It
waits for the engine's lock, which ``step`` holds through each decode step
and each admission prefill."""
import statistics


def read(run):
    waits = [c.wait_s for c in run.calls if c.kind == "submit"]
    return 1e3 * statistics.median(waits) if waits else None
