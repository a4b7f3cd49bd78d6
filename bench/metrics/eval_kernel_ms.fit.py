"""Device time of the Mosaic (Pallas) kernels per generation: the
summed durations of Mosaic custom-call events in the traced window,
mean over chips, over the generations traced."""


def read(ctx):
    t, gens = ctx["trace"], ctx["work"].get("traced_generations")
    if t is None or not gens:
        return None
    s = t.kind_mean_s("mosaic")
    return 1000.0 * s / gens if s > 0 else None
