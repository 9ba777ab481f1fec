"""Peer exchange, in ms per population step: the traced window's time under
the program's ``mule_peer`` scope, less the training that nests in it, by
``bench/layers.py``'s split, averaged over chips, over the steps traced.
Nothing to read where the method has no peer exchange."""


def read(ctx):
    return ctx["layers"].get("peer_ms_per_step")
