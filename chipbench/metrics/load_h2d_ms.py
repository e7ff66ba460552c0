"""Checkout host→device uploads: milliseconds per checkout in the ``h2d``
spans (each loaded leaf put on the device, each scatter's rows and
indices).  Moves ``checkout_s``."""


def read(ctx):
    spans = ctx.named("h2d")
    if not ctx.n_checkouts or not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx.n_checkouts
