"""Model decode step: the live rows' operations, counted from shapes, over
the device time of the traced decode programs times the peak FLOP/s."""


def read(run):
    pairs = run.matched("decode")
    t = sum(ev.dur for ev, _ in pairs)
    if not t:
        return None
    flops = sum(run.cost(c)[0] for _, c in pairs)
    return 100.0 * flops / (t * run.peak["flops"])
