"""Share of the traced window in which no operation ran on the device,
as the mean over the chips the fit cell uses (1 - union of busy
intervals / window)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or t.window_s <= 0 or t.mean(t.busy_s) <= 0:
        return None
    return 100.0 * (1.0 - t.mean(t.busy_s) / t.window_s)
