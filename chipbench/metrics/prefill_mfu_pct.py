"""Model prefill step: the prefills' operations, counted from shapes by the
configuration's reference module, over the device time of the traced
admission programs times the chip's peak FLOP/s."""


def read(run):
    pairs = run.matched("prime")
    t = sum(ev.dur for ev, _ in pairs)
    if not t:
        return None
    flops = sum(run.cost(c)[0] for _, c in pairs)
    return 100.0 * flops / (t * run.peak["flops"])
