"""Writer device→host copies: milliseconds per commit in the ``d2h`` spans
(each blocking copy of a pack segment's compacted rows, of its codec masks
or planes, or of a whole leaf serialized on the host path).  Moves
``commit_s``."""


def read(ctx):
    spans = ctx.named("d2h")
    if not ctx.n_commits or not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx.n_commits
