"""Full-load reads: milliseconds per checkout in the ``read`` spans (the
chunks of fully diverged co-variables read from the store and their frames
decoded, on the checkout's own thread).  Moves ``checkout_s``."""


def read(ctx):
    spans = ctx.named("read")
    if not ctx.n_checkouts or not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx.n_checkouts
