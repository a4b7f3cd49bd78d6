"""Device idle per generation at the block boundaries of the session's
host loop: the traced window's idle time under the program's
`fit.block` span, its children `fit.dispatch` (the jitted block call
returning) and `fit.sync` (the boundary `device_get`), and `fit.absorb`
(the host's bookkeeping after the sync), over the generations traced. A
trace that holds none of these spans gives nothing."""

SPANS = ("fit.block", "fit.dispatch", "fit.sync", "fit.absorb")


def read(ctx):
    t, gens = ctx["trace"], ctx["work"].get("traced_generations")
    if t is None or not gens or not any(n in SPANS for n, _, _ in t.spans):
        return None
    gaps = dict(t.idle_gaps)
    return 1000.0 * sum(gaps.get(n, 0.0) for n in SPANS) / gens
