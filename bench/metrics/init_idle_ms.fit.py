"""Device idle per generation while the session builds a fresh state:
the traced window's idle time under the program's `fit.init_state` span
and its children (`fit.init_population`, `fit.init_restore`), over the
generations traced. A trace that holds none of these spans (a program
that does not annotate them) gives nothing."""

SPANS = ("fit.init_state", "fit.init_population", "fit.init_restore")


def read(ctx):
    t, gens = ctx["trace"], ctx["work"].get("traced_generations")
    if t is None or not gens or not any(n in SPANS for n, _, _ in t.spans):
        return None
    gaps = dict(t.idle_gaps)
    return 1000.0 * sum(gaps.get(n, 0.0) for n in SPANS) / gens
