"""The whole checkout's share of the chip's peak: the least time to write,
once, the bytes in which the current and the target state differ (the
cells the checkout undoes), over the mean checkout wall time.  Moves
``checkout_s``."""
from chipbench import peaks


def read(ctx):
    mean = ctx.checkout_s()
    if not mean:
        return None
    least = sum(peaks.least_time_s(ctx.peak,
                                   hbm_bytes=ctx.checkout_bytes(o))
                for o in ctx.checkouts) / ctx.n_checkouts
    return 100.0 * least / mean
