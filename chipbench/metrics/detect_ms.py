"""Detection: milliseconds per commit in the session's ``detect`` spans
(hashing, the fused device pack, Lemma-1 pruning).  Moves ``commit_s``."""


def read(ctx):
    spans = ctx.named("detect")
    if not ctx.n_commits or not spans:
        return None
    return 1e3 * sum(s["dur"] for s in spans) / ctx.n_commits
