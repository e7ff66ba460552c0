"""Device time per commit: milliseconds in which an operation ran on the
device (the union of the ``XLA Ops`` intervals) inside the window's ``run``
calls, over the commits.  Copies between host and device are not
operations and do not count.  Moves ``commit_s``."""
from chipbench import trace


def read(ctx):
    spans = ctx.annotated("commit")
    if not spans or not ctx.trace.devices:
        return None
    busy = sum(trace.overlap(ctx.busy(), a, b) for a, b in spans)
    return 1e-6 * busy / len(spans) if busy > 0 else None
