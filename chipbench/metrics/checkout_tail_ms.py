"""The device tail of a checkout: milliseconds per checkout from the end of
the program's ``kishu.checkout`` event to the end of the benchmark's
``chipbench.checkout`` annotation around it, which waits until every
restored array is ready — device work the checkout dispatched and returned
before.  Moves ``checkout_s``."""
from chipbench import program_trace


def read(ctx):
    if not ctx.n_checkouts:
        return None
    ends = [ev.t1 for _, ev in
            program_trace.joined(ctx, ctx.roots("checkout"))]
    tails = []
    for a, b in ctx.annotated("checkout"):
        inside = [t for t in ends if a <= t <= b]
        if inside:
            tails.append(b - max(inside))
    return 1e-6 * sum(tails) / len(tails) if tails else None
