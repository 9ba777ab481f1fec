"""Local training, in ms per population step: the traced window's time
under the program's ``mule_train`` scope (the batch draw, forward,
backward, update and keep-mix), by ``bench/layers.py``'s split, averaged
over chips, over the steps traced. Nothing to read where the program names
no such scope."""


def read(ctx):
    return ctx["layers"].get("local_train_ms_per_step")
