"""Device idle share over the commits: 1 - busy/time over the intervals of
the window's ``run`` calls, busy being the union of device operations in
the trace.  Moves ``commit_s``."""
from chipbench import trace


def read(ctx):
    share = trace.idle_share(ctx.busy(), ctx.annotated("commit"))
    return None if share is None or not ctx.trace.devices else 100.0 * share
