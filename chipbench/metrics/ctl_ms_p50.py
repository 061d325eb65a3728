"""Control plane (gateway, scheduler, orchestrator): the median of the
client's latency, from the due time, less the adapter's ``output.total_ms``
— the time a request spends outside the serving engine, both ways."""
import math


def read(run):
    v = run.stats.get("ctl_ms_p50")
    return None if v is None or math.isnan(v) else v
