"""Device→host megabytes per commit: the ``bytes`` of the ``pack_sync``
spans (each segment's count, flags and hashes) and of the ``d2h`` spans
(rows, codec masks and planes, whole leaves).  Moves ``commit_s``."""


def read(ctx):
    spans = ctx.named("pack_sync") + ctx.named("d2h")
    if not ctx.n_commits or not spans:
        return None
    return 1e-6 * sum(s["args"]["bytes"] for s in spans) / ctx.n_commits
