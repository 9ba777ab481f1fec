"""Device idle share of the traced window, in %: 1 - busy / window, with
busy the union of a chip's operation intervals averaged over chips."""


def read(ctx):
    s = ctx["summary"]
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
