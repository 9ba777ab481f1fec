"""Space aggregation and send-back, in ms per population step: the traced
window's time under the program's ``mule_space`` scope, by
``bench/layers.py``'s split, averaged over chips, over the steps traced.
Nothing to read where the method has no space exchange."""


def read(ctx):
    return ctx["layers"].get("space_ms_per_step")
