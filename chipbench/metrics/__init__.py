"""Per-layer metric readers, one module per metric, found by name.

Each exposes ``read(run) -> float | None``: ``run`` is the harness's
record of one traced run (``chipbench.harness.record.Run``).  A reader
that finds nothing to read returns None and the metric is left out.
"""
