"""Share of its roofline that the ``patch_scatter`` kernel reached: the
least time to land the dirty chunks each call was given (read once, written
once, without the power-of-two padding rows), over the kernel's summed
device time in the trace.  Moves ``checkout_s``."""
from chipbench import peaks

KERNEL_NAMES = ("%patch_scatter_pallas",)


def read(ctx):
    spans = ctx.named("scatter_dev")
    t = ctx.kernel_time_s(KERNEL_NAMES)
    if not spans or t <= 0:
        return None
    need = sum(peaks.patch_scatter_bytes(int(s["args"]["chunks"]),
                                         ctx.chunk_bytes) for s in spans)
    return 100.0 * peaks.least_time_s(ctx.peak, hbm_bytes=need) / t
