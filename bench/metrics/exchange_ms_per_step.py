"""Exchange and colocation, in ms per population step: device time of
every operation not under the autodiff of the mule loss (space cycle,
freshness push, aggregation, send-back, peer mix, schedule expansion, the
SGD update), averaged over chips, over the steps traced."""


def read(ctx):
    s = ctx["summary"]
    if not s or s["exchange_s"] <= 0:
        return None
    return 1e3 * s["exchange_s"] / s["steps"]
