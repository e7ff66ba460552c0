"""Share of its roofline that the ``delta_pack`` kernel reached: the least
time for the bytes it must read and write (``peaks.delta_pack_bytes`` of
each call's array, with the chunks the window's cells dirtied), over the
kernel's summed device time in the trace.  Moves ``commit_s``."""
from chipbench import peaks

KERNEL_NAMES = ("%delta_pack_pallas",)


def read(ctx):
    spans = ctx.named("delta_pack")
    t = ctx.kernel_time_s(KERNEL_NAMES)
    if not spans or t <= 0:
        return None
    cb = ctx.chunk_bytes
    dirty_bytes = sum(ctx.cell_dirty_bytes(o.commit) for o in ctx.commits)
    need = sum(peaks.delta_pack_bytes(int(s["args"]["nbytes"]), cb, 0)
               for s in spans) + dirty_bytes
    return 100.0 * peaks.least_time_s(ctx.peak, hbm_bytes=need) / t
