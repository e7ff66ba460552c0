"""Host→device megabytes per checkout: the ``bytes`` of the ``h2d`` spans
(loaded leaves, scatter rows and indices).  Moves ``checkout_s``."""


def read(ctx):
    spans = ctx.named("h2d")
    if not ctx.n_checkouts or not spans:
        return None
    return 1e-6 * sum(s["args"]["bytes"] for s in spans) / ctx.n_checkouts
