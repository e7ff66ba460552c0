"""The reduction from a profiler trace to busy time, idle shares, kernel
times and attributed idle gaps: on intervals made up here, and on a small
trace recorded on the chip."""
from pathlib import Path

import pytest

import chipbench_testkit  # noqa: F401  (puts the checkout on sys.path)
from chipbench import trace


def test_union_merges_overlaps_and_drops_empties():
    got = trace.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9), (10, 12)])
    assert got == [(0, 3), (5, 8), (10, 12)]


def test_overlap_and_idle_share():
    busy = trace.union([(0, 2), (4, 6), (8, 10)])
    assert trace.overlap(busy, 1, 9) == 1 + 2 + 1
    assert trace.overlap(busy, 10, 20) == 0
    assert trace.idle_share(busy, [(0, 10)]) == pytest.approx(0.4)
    assert trace.idle_share(busy, [(0, 2), (2, 4)]) == pytest.approx(0.5)
    assert trace.idle_share(busy, []) is None


def test_gaps_and_their_labels():
    busy = trace.union([(2, 3), (5, 6)])
    assert trace.gaps(busy, 0, 10) == [(0, 2), (3, 5), (6, 10)]
    labels = {(0, 2): "commit/detect", (3, 5): "commit/detect",
              (6, 10): "checkout/fetch"}
    got = trace.idle_by_label(busy, 0, 10,
                              lambda gs: [labels[g] for g in gs])
    assert got == [["commit/detect", 4e-9], ["checkout/fetch", 4e-9]]


def test_kernel_time_and_top_ops_average_over_devices():
    k = "%delta_pack_pallas.2 = (s32[64,1,128]) custom-call(u32[64] %p)"
    user = "%fusion.1 = u32[64] fusion(%delta_pack_pallas.2)"
    tr = trace.Trace(ops={"/device:TPU:0": [(k, 0, 10), (user, 10, 40)],
                          "/device:TPU:1": [(k, 0, 30)]})
    assert trace.kernel_time_s(tr, ["%delta_pack_pallas"]) == 20e-9
    assert trace.top_ops(tr, 2) == [[k, 20e-9], [user, 15e-9]]


# A trace recorded on one TPU v5 lite by
# `chipbench/run.py --workload mamba2_explore_sparse --seed 2104 --seconds 1
#  --trace 1 --keep-trace <dir>`: one edit commit and the flush.
RECORDED = Path(__file__).resolve().parent / "data" / \
    "explore_sparse_1s.xplane.pb"


@pytest.fixture(scope="module")
def recorded():
    return trace.load(str(RECORDED))


def test_recorded_trace_planes_and_annotations(recorded):
    assert recorded.devices == ["/device:TPU:0"]
    assert [a[0] for a in recorded.annotations] == ["window", "commit",
                                                    "flush"]
    win = recorded.annotations[0]
    for _, a, b in recorded.annotations[1:]:
        assert win[1] <= a <= b <= win[2]


def test_recorded_trace_busy_and_idle(recorded):
    busy = recorded.busy("/device:TPU:0")
    _, lo, hi = recorded.annotations[0]
    in_window = trace.overlap(busy, lo, hi)
    assert 0 < in_window < hi - lo
    idle = trace.gaps(busy, lo, hi)
    assert sum(b - a for a, b in idle) == pytest.approx(hi - lo - in_window)
    commit = [(a, b) for n, a, b in recorded.annotations if n == "commit"]
    share = trace.idle_share(busy, commit)
    assert 0 < share < 1
    labelled = trace.idle_by_label(busy, lo, hi,
                                   lambda gs: ["idle"] * len(gs))
    assert labelled[0][1] == pytest.approx((hi - lo - in_window) / 1e9)


def test_recorded_trace_kernels(recorded):
    from jax.profiler import ProfileData

    # the kernel's events, summed straight from the file
    want = 0.0
    for plane in ProfileData.from_file(str(RECORDED)).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    want += sum(e.duration_ns for e in line.events
                                if e.name.startswith("%delta_pack_pallas"))
    got = trace.kernel_time_s(recorded, ["%delta_pack_pallas"])
    assert got == pytest.approx(want / 1e9) and got > 0
    assert trace.kernel_time_s(recorded, ["%codec_encode_pallas"]) > 0
    names = [trace.instruction(n) for n, _ in trace.top_ops(recorded)]
    assert len(names) == 10 and all(n.startswith("%") for n in names)


def test_recorded_trace_programs(recorded):
    from jax.profiler import ProfileData

    want = 0.0
    for plane in ProfileData.from_file(str(RECORDED)).planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    want += sum(e.duration_ns for e in line.events
                                if e.name.startswith("jit__pack_words"))
    packing = trace.module_busy(recorded, "/device:TPU:0",
                                ["jit__pack_words"])
    assert sum(b - a for a, b in packing) == pytest.approx(want) and want > 0
    assert trace.module_busy(recorded, "/device:TPU:0", ["jit_nothing"]) \
        == []


class _Ctx:
    """What the device-time readers see of a run: the recorded trace."""

    def __init__(self, tr):
        self.trace = tr

    def annotated(self, name):
        return [(a, b) for n, a, b in self.trace.annotations if n == name]

    def busy(self):
        return self.trace.busy(self.trace.devices[0])


def test_device_time_readers_on_the_recorded_trace(recorded):
    from chipbench import bench

    ctx = _Ctx(recorded)
    device = bench.load_reader("device_ms.commit.sparse")(ctx)
    packing = bench.load_reader("word_pack_ms.sparse")(ctx)
    (_, a, b), = [x for x in recorded.annotations if x[0] == "commit"]
    assert 0 < packing <= device <= (b - a) / 1e6
    busy = trace.overlap(ctx.busy(), a, b)
    assert device == pytest.approx(busy / 1e6)
    # the recorded window holds no checkout: nothing to read
    assert bench.load_reader("device_ms.checkout.sparse")(ctx) is None
