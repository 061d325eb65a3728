"""Flash-attention kernel (``kernels/flash_attention``) against its
roofline: each traced kernel event inside a traced admission program,
priced by that prefill's length with the reference module's
``flash_cost``; the summed least times over the summed kernel time."""


def read(run):
    if run.trace is None or not run.fam.flash_calls_per_prefill(run.cfg):
        return None
    kernels = run.trace.kernels("flash")
    least = t = 0.0
    for ev, call in run.matched("prime"):
        cost = run.fam.flash_cost(run.cfg, call.prompt_len)
        for k in kernels:
            if ev.start <= k.start < ev.start + ev.dur:
                least += run.roofline_s(*cost)
                t += k.dur
    return 100.0 * least / t if t else None
