"""The benchmark is driven by data: a cell, a configuration, a mix and a
per-layer metric are found by name, so that a later cell is added with new
files only; and BENCHMARK.json keeps to its contract."""
import hashlib
import json
import re
import shutil

import pytest

import chipbench_testkit as kit

ROOT = kit.ROOT
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _digests(base):
    return {p.relative_to(base).as_posix(): hashlib.sha256(
        p.read_bytes()).hexdigest()
        for p in sorted(base.rglob("*")) if p.is_file()}


def test_a_new_cell_resolves_from_new_files_only(tmp_path):
    from chipbench import bench

    base = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics", "ops"):
        shutil.copytree(ROOT / "chipbench" / sub, base / sub)
    before = _digests(base)
    # a later PR's additions: a mix, a reader and a cell entry
    mix = json.loads((base / "traffic" / "explore_sparse.json").read_text())
    mix.update(name="explore_wide", cell=dict(mix["cell"], rows=64))
    (base / "traffic" / "explore_wide.json").write_text(json.dumps(mix))
    (base / "metrics" / "edits_per_s.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench_doc = json.loads(json.dumps(BENCH))
    bench_doc["workloads"].append(
        {"name": "mamba2_explore_wide", "config": "mamba2_780m",
         "traffic": "explore_wide", "chips": 1, "why": "wider edits"})
    bench_doc["per_layer"].append(
        {"name": "edits_per_s", "unit": "1/s", "better": "higher",
         "source": "host_clock", "layer": "checkout",
         "moves": "commit_s.sparse", "workloads": ["mamba2_explore_wide"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench_doc))

    cell = bench.load_cell("mamba2_explore_wide", bench_path=path, base=base)
    assert cell.traffic["cell"]["rows"] == 64
    assert cell.config["name"] == "mamba2_780m"
    assert "edits_per_s" in [m["name"] for m in cell.per_layer]
    assert bench.load_reader("edits_per_s", base)(None) == 42.0
    old = bench.load_cell("mamba2_explore_sparse", bench_path=path,
                          base=base)
    assert "edits_per_s" not in [m["name"] for m in old.per_layer]
    after = _digests(base)
    assert {k: after[k] for k in before} == before


SET_LR_OP = '''
from chipbench.traffic import CellOp


def set_lr(ns, scale):
    ns["hparams/lr"] = 3e-4 * scale


class Op(CellOp):
    def args(self, k):
        return {"scale": 1.0 / (k + 2)}

    def warmup_args(self, i):
        return {"scale": 2.0 + i}

    def command(self, cells):
        return set_lr
'''


def test_a_new_op_runs_from_new_files_only(tmp_path, capsys):
    """A mix whose cells are a new op: its file under ``ops/``, a mix and a
    cell entry, and the window runs and checks it on a tiny state."""
    base = tmp_path / "chipbench"
    for sub in ("configs", "traffic", "metrics", "ops"):
        shutil.copytree(ROOT / "chipbench" / sub, base / sub)
    before = _digests(base)
    (base / "ops" / "set_lr.py").write_text(SET_LR_OP)
    mix = {"name": "tune_lr", "setup": {"train_steps": 1, "edits": 2},
           "cell": {"op": "set_lr"},
           "checkout": {"every": 2, "back_min": 1, "back_max": 2},
           "readback": 2, "warmup": {"cells": 1, "checkouts": [1, 2]}}
    (base / "traffic" / "tune_lr.json").write_text(json.dumps(mix))
    bench_doc = json.loads(json.dumps(BENCH))
    bench_doc["workloads"].append(
        {"name": "qwen3_tune_lr", "config": "qwen3_1p7b",
         "traffic": "tune_lr", "chips": 1, "why": "learning-rate edits"})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench_doc))
    after = _digests(base)
    assert {k: after[k] for k in before} == before

    out, run = kit.run_tiny("qwen3_tune_lr", seconds=1.0, bench_path=path,
                            base=base)
    assert out["correct"] is True and out["failed"] == 0
    assert run.window_compiles == 0
    assert out["checks"]["checkouts_differing"]["of"] >= 1
    cmds = {cmd for cmd, _ in run.history.cell.values()}
    assert cmds == {"set_lr"}
    assert "window compiles: 0" in capsys.readouterr().out


def test_an_unknown_op_is_refused():
    from chipbench.traffic import Traffic

    spec = json.loads((ROOT / "chipbench" / "traffic"
                       / "explore_sparse.json").read_text())
    spec["cell"] = {"op": "no_such_op"}
    with pytest.raises(ValueError, match="no_such_op"):
        Traffic(spec, {"vocab_size": 64}, 1)


def test_every_named_file_exists():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert (ROOT / "chipbench" / "configs"
                / f"{cfg['architecture']}.py").is_file()
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in BENCH["workloads"]:
        mix = json.loads((ROOT / "chipbench" / "traffic"
                          / f"{w['traffic']}.json").read_text())
        assert (ROOT / "chipbench" / "ops"
                / f"{mix['cell']['op']}.py").is_file()
    from chipbench import bench

    for m in BENCH["per_layer"]:
        assert bench.reader_path(m["name"]) is not None, m["name"]


def test_a_tag_splits_a_quantity_into_metrics_of_its_own():
    from chipbench import bench

    known = {"commit_s", "device_idle.commit"}.__contains__
    assert bench.quantity("commit_s", known) == "commit_s"
    assert bench.quantity("commit_s.sparse", known) == "commit_s"
    assert bench.quantity("device_idle.commit", known) == "device_idle.commit"
    assert bench.quantity("device_idle.commit.rollback", known) == \
        "device_idle.commit"
    assert bench.quantity("checkout_s.sparse", known) is None
    assert bench.reader_path("detect_ms.sparse").name == "detect_ms.py"
    assert bench.reader_path("device_idle.checkout.rollback").name == \
        "device_idle.checkout.py"
    assert bench.reader_path("no_such_metric.sparse") is None


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert all(w["chips"] in (1, 4) for w in cells.values())
    assert {w["config"] for w in cells.values()} == \
        {c["name"] for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            moved = e2e[m["moves"]]
            assert w in moved.get("workloads", cells)
    for w in cells:
        reports = [m for m in BENCH["end_to_end"]
                   if w in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in reports] and len(reports) > 1
        assert any(w in m.get("workloads", cells) for m in BENCH["per_layer"])
    # every later check fits its time with the full 24 cells
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_reader_is_silent_when_there_is_nothing_to_read(metric):
    from chipbench import bench

    class Empty:
        n_commits = n_checkouts = 0
        commits = checkouts = []
        chunk_bytes = 1 << 16
        trace = type("T", (), {"devices": [], "ops": {}, "modules": {},
                               "annotations": []})()

        def named(self, name):
            return []

        def roots(self, name):
            return []

        def kernel_time_s(self, names):
            return 0.0

        def busy(self):
            return []

        def annotated(self, name):
            return []

        def commit_s(self):
            return None

        def checkout_s(self):
            return None

    assert bench.load_reader(metric)(Empty()) is None
