"""Writer chunk keys and dedup: milliseconds per commit of the ``keys_s``
(blake2b key of each chunk) and ``has_s`` (the store's dedup lookup of each
key) the ``manifest`` spans sum over their chunks.  Moves ``commit_s``."""


def read(ctx):
    spans = [s for s in ctx.named("manifest") if "keys_s" in s["args"]]
    if not ctx.n_commits or not spans:
        return None
    tot = sum(s["args"]["keys_s"] + s["args"]["has_s"] for s in spans)
    return 1e3 * tot / ctx.n_commits
