"""Checkout plan and fetch: milliseconds per checkout in the ``plan`` and
``fetch`` children of each ``checkout`` span (the graph diff, the patch
plan, the dirty chunks read from the store).  Moves ``checkout_s``."""


def read(ctx):
    roots = ctx.roots("checkout")
    if not roots:
        return None
    tot = sum(c["dur"] for r in roots
              for c in ctx.children(r, ("plan", "fetch")))
    return 1e3 * tot / len(roots)
