"""The whole commit's share of the chip's peak: the least time the chip
needs for a commit's cell and commit (the larger of the cell's model
operations over peak FLOP/s and the bytes it changes, read once, over peak
HBM bytes/s), over the mean commit wall time.  Moves ``commit_s``."""
from chipbench import peaks


def read(ctx):
    mean = ctx.commit_s()
    if not mean:
        return None
    least = sum(peaks.least_time_s(ctx.peak, flops=ctx.cell_flops(o.commit),
                                   hbm_bytes=ctx.cell_dirty_bytes(o.commit))
                for o in ctx.commits) / ctx.n_commits
    return 100.0 * least / mean
