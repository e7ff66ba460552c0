"""Detection's host syncs: milliseconds per commit in the ``pack_sync``
spans, where the host waits for each segment's dirty count, flags and
hashes of the fused delta pass.  Moves ``commit_s``."""


def read(ctx):
    spans = ctx.named("pack_sync")
    if not ctx.n_commits or not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx.n_commits
