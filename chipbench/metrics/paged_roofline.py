"""Paged decode attention kernel (``kernels/paged_decode.py``) against its
roofline: each traced kernel event inside a traced decode program, priced
by that step's live rows with :func:`cost` (one layer's call); the summed
least times over the summed kernel time.  The count is of the keys each row
attends over, not of the pages read, so the share cannot pass 100%."""


def cost(cfg, contexts):
    """(FLOPs, bytes) of one paged decode attention call (one layer) over
    live rows whose tokens sit at ``contexts``: QK and PV over each row's
    ctx + 1 keys; those keys' K and V read once, each row's query read and
    output written, bf16."""
    a = cfg["arch"]
    H, K = a["num_heads"], a["num_kv_heads"]
    hd = a["d_model"] // H
    keys = sum(int(c) + 1 for c in contexts)
    qo = 2 * len(contexts) * H * hd * 2
    return 4 * H * hd * keys, 2 * keys * K * hd * 2 + qo


def read(run):
    if run.trace is None:
        return None
    kernels = run.trace.kernels("paged_decode")
    least = t = 0.0
    for ev, call in run.matched("decode"):
        for k in kernels:
            if ev.start <= k.start < ev.start + ev.dur:
                least += run.roofline_s(*cost(run.cfg, call.contexts))
                t += k.dur
    return 100.0 * least / t if t else None
