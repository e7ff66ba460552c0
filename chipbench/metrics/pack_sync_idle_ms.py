"""Device idle inside detection's host syncs: milliseconds per commit,
within the ``kishu.pack_sync`` events of the trace, in which no operation
ran on the device (``ctx.busy()``): the host waiting on a device that has
nothing to do, for the copies and the round trip.  Moves ``commit_s``."""
from chipbench import program_trace, trace


def read(ctx):
    spans = ctx.named("pack_sync")
    if not ctx.n_commits or not spans or not ctx.trace.devices:
        return None
    pairs = program_trace.joined(ctx, spans)
    if not pairs:
        return None
    busy = ctx.busy()
    idle = sum(ev.t1 - ev.t0 - trace.overlap(busy, ev.t0, ev.t1)
               for _, ev in pairs)
    return 1e-6 * idle / ctx.n_commits
