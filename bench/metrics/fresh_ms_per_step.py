"""Freshness filter and push, in ms per population step: the traced
window's time under the program's ``mule_fresh`` scope, its loop's waits
included, by ``bench/layers.py``'s split, averaged over chips, over the
steps traced. Nothing to read where the method keeps no freshness state."""


def read(ctx):
    return ctx["layers"].get("fresh_ms_per_step")
