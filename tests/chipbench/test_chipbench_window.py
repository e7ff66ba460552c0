"""Each traffic mix's set-up and window at a size the CPU holds: the
session's commits and checkouts restore bit for bit what the plain
reference computes, the window compiles nothing, and every metric the
cell reports comes out."""
import json

import pytest

import chipbench_testkit as kit


@pytest.fixture(autouse=True)
def _kernels_on(monkeypatch):
    for gate in kit.KERNEL_GATES:
        monkeypatch.setenv(gate, "1")


@pytest.mark.parametrize("name", kit.CELLS)
def test_window_is_correct_and_reports_its_metrics(name, capsys):
    out, run = kit.run_tiny(name)
    assert out["correct"] is True
    assert out["failed"] == 0
    checks = out["checks"]
    assert list(out)[-1] == "checks"
    assert checks["checkouts_differing"]["of"] >= 1
    assert checks["readbacks_differing"]["of"] >= 1
    assert run.window_compiles == 0
    want = {m["name"] for m in run.cell.end_to_end}
    assert set(out["metrics"]) == want
    tag = {"qwen3_train_rollback": "rollback",
           "mamba2_explore_sparse": "sparse"}[name]
    assert out["metrics"][f"commit_s.{tag}"]["value"] == pytest.approx(
        (sum(o.seconds for o in run.window.of("commit"))
         + run.window.flush_s) / len(run.window.of("commit")))
    for m in out["metrics"].values():
        assert m["value"] > 0
    json.dumps(out)
    assert "window compiles: 0" in capsys.readouterr().out


@pytest.mark.parametrize("name", kit.CELLS)
def test_traced_window_reads_the_spans(name, tmp_path):
    out, run = kit.run_tiny(name, trace=True, trace_dir=str(tmp_path))
    assert out["correct"] is True
    from chipbench import bench

    got = {bench.reader_path(n).stem for n in out["metrics"]}
    # the CPU has no TPU plane: only the span and host-clock readers speak
    assert {"detect_ms", "write_ms", "checkout_fetch_ms",
            "checkout_apply_ms", "commit_mfu", "checkout_mfu"} <= got
    assert not got & {"delta_pack_roofline", "patch_scatter_roofline",
                      "device_idle.commit", "device_idle.checkout"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(out["device"])
    for m in out["metrics"].values():
        assert m["value"] > 0


def test_rollback_cells_and_checkouts_follow_the_mix():
    out, run = kit.run_tiny("qwen3_train_rollback", seconds=6.0)
    ops = run.window.ops
    kinds = "".join("c" if o.kind == "commit" else "k" for o in ops)
    assert kinds.startswith("cck")
    for o in run.window.of("checkout"):
        assert o.commit == run.history.attach     # 2 back: the round start
        assert len(o.undone) == 2
    lrs = [run.history.cell[o.commit][1]["lr_scale"]
           for o in run.window.of("commit")]
    assert lrs[:4] == [1.0, 1.0, 0.5, 0.5][:len(lrs)]


def test_sparse_edits_never_repeat_a_block_and_stay_after_the_attach():
    out, run = kit.run_tiny("mamba2_explore_sparse", seconds=2.0)
    blocks = [a["block"] for c, (cmd, a) in run.history.cell.items()
              if cmd == "edit_rows"]
    assert len(blocks) == len(set(blocks))
    for o in run.window.of("checkout"):
        assert 1 <= len(o.undone) <= 8


@pytest.mark.parametrize("seed", [1, 4100000004, 2**31 + 17])
def test_sparse_setup_edits_show_every_segment_pattern(seed):
    """The set-up's edits begin with blocks that between them touch each
    leaf's segments in every way a window edit can: a block that straddles
    a segment, and one in the short last segment, compile in set-up."""
    import numpy as np

    from chipbench import bench
    from chipbench.traffic import Traffic
    from repro.kernels.delta_pack.ops import DEFAULT_SEG_BYTES

    cell = bench.load_cell("mamba2_explore_sparse")
    cfg, spec = cell.config, cell.traffic
    rows, chunk = spec["cell"]["rows"], bench.CHUNK_BYTES
    seg = DEFAULT_SEG_BYTES // chunk
    pad = cfg["pad_vocab_size_multiple"]
    table = -(-cfg["vocab_size"] // pad) * pad
    leaves = [(table, cfg["d_model"] * item) for item in (2, 4, 4)]
    t = Traffic(spec, cfg, seed)
    order = list(t.op.blocks)
    sizes = {f"state/{kind}/embed": n * width for kind, (n, width)
             in zip(("params", "opt/mu", "opt/nu"), leaves)}
    shapes = {name: (table, size // table) for name, size in sizes.items()}
    t.prepare(sizes, shapes, chunk)
    picked = list(t.op.blocks[:t.n_setup])

    def touched(block):
        out = set()
        for li, (n, width) in enumerate(leaves):
            lo, hi = block * rows * width, (block + 1) * rows * width
            byte = np.append(np.arange(lo, hi, chunk // 4), hi - 1)
            segs = np.unique(byte // chunk) // seg
            last = (-(-n * width // chunk) - 1) // seg
            for s, k in zip(*np.unique(segs, return_counts=True)):
                out.add((li, bool(s == last), int(k)))
        return out

    every = set().union(*(touched(b) for b in order))
    assert set().union(*(touched(b) for b in picked)) == every
    assert any(k < 3 for li, _, k in every if li == 1)   # straddles occur
    assert sorted(t.op.blocks) == sorted(order)
    assert t.setup_edits() >= len(picked) > 0
    assert [t.next_cell()[1]["block"] for _ in picked] == picked
