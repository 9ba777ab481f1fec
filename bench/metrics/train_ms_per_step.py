"""Local training, in ms per population step: device time of operations
whose JAX name stack lies under the autodiff of the mule loss (``jvp(`` or
``transpose(``), averaged over chips, over the steps traced."""


def read(ctx):
    s = ctx["summary"]
    if not s or s["train_s"] <= 0:
        return None
    return 1e3 * s["train_s"] / s["steps"]
