"""Writer and transactions: milliseconds per commit in each ``commit`` span
outside its ``exec`` and ``detect`` children (serialization, chunk puts,
the epoch fence, the publish).  Moves ``commit_s``."""


def read(ctx):
    roots = ctx.roots("commit")
    if not roots:
        return None
    tot = sum(r["dur"] - sum(c["dur"] for c in
                             ctx.children(r, ("exec", "detect")))
              for r in roots)
    return 1e3 * tot / len(roots)
