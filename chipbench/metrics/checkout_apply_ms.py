"""Checkout apply: milliseconds per checkout in each ``checkout`` span
outside its ``plan`` and ``fetch`` children (full loads, patches through
the device scatter, the swap into the namespace).  Moves ``checkout_s``."""


def read(ctx):
    roots = ctx.roots("checkout")
    if not roots:
        return None
    tot = sum(r["dur"] - sum(c["dur"] for c in
                             ctx.children(r, ("plan", "fetch")))
              for r in roots)
    return 1e3 * tot / len(roots)
