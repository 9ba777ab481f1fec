"""Exposed collective time, in ms per population step: time in which a
chip runs a collective (the all-gather of ``ordered_psum``, a ring
``ppermute``) and no other operation, averaged over chips, over the steps
traced. Nothing to read in a trace without collectives."""


def read(ctx):
    s = ctx["summary"]
    if not s or not s["has_collectives"]:
        return None
    return 1e3 * s["collective_exposed_s"] / s["steps"]
