"""Device busy time per generation outside the eval kernels (breeding,
selection, the dedup plan, merges): the union of busy intervals minus
the Mosaic kernels' time, mean over chips, over the traced
generations."""


def read(ctx):
    t, gens = ctx["trace"], ctx["work"].get("traced_generations")
    if t is None or not gens or t.mean(t.busy_s) <= 0:
        return None
    other = t.mean(t.busy_s) - t.kind_mean_s("mosaic")
    return 1000.0 * max(other, 0.0) / gens
