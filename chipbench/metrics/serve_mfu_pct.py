"""Whole window: the operations of every prefill and decode step
dispatched in the measured window, counted from shapes, over the window's
length times the chip's peak FLOP/s."""


def read(run):
    steps = [c for c in run.calls if c.kind in ("prime", "decode")]
    if not steps:
        return None
    flops = sum(run.cost(c)[0] for c in steps)
    return 100.0 * flops / (run.seconds * run.peak["flops"])
