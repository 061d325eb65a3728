"""Model decode step against its roofline: for each traced decode program,
the least time the chip could take — the larger of its operations over the
peak FLOP/s and its bytes (weights read once, each live row's KV cache or
recurrent state read and written) over the peak bandwidth — summed, over
the summed device time.  At these batch sizes the bytes bound it."""


def read(run):
    pairs = run.matched("decode")
    t = sum(ev.dur for ev, _ in pairs)
    if not t:
        return None
    least = sum(run.roofline_s(*run.cost(c)) for _, c in pairs)
    return 100.0 * least / t
