"""Device idle share over the checkouts: 1 - busy/time over the intervals
of the window's ``checkout`` calls.  Moves ``checkout_s``."""
from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx.busy(), ctx.annotated("checkout"))
    return None if share is None or not ctx.trace.devices else 100.0 * share
