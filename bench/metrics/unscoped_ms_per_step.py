"""Unattributed, in ms per population step: the traced window's time under
none of the program's layer scopes (the scan's bookkeeping, key folds, work
the compiler moved out of a scope, the gaps between chunk programs), by
``bench/layers.py``'s split, averaged over chips, over the steps traced.
Nothing to read where the program names no layer."""


def read(ctx):
    return ctx["layers"].get("unscoped_ms_per_step")
