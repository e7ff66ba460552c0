"""Word packing per commit: milliseconds of device time, inside the
window's ``run`` calls, of the program that lays each array out as rows of
32-bit words for the detection pass (``_pack_words`` of
``kernels/delta_pack``, named ``jit__pack_words`` in the trace), over the
commits.  Moves ``commit_s``."""
from chipbench import trace

PROGRAMS = ("jit__pack_words",)


def read(ctx):
    spans = ctx.annotated("commit")
    if not spans or not ctx.trace.devices:
        return None
    plane = ctx.trace.devices[0]
    packing = trace.module_busy(ctx.trace, plane, PROGRAMS)
    t = sum(trace.overlap(packing, a, b) for a, b in spans)
    return 1e-6 * t / len(spans) if t > 0 else None
