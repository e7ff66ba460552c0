"""Shared by the benchmark's tests: its cells at a size a CPU test run can
hold, run in this process with the chip check left out."""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CELLS = ("qwen3_train_rollback", "mamba2_explore_sparse")
KERNEL_GATES = ("KISHU_DEVICE_DELTA", "KISHU_DEVICE_HASH",
                "KISHU_DEVICE_SCATTER")


def tiny_cell(name: str, bench_path=None, base=None):
    """The cell with every width cut, the depth and the traffic as
    published."""
    from chipbench import bench

    cell = bench.load_cell(name, bench_path=bench_path,
                           base=base or bench.HERE)
    cfg = dict(cell.config)
    if cfg["architecture"] == "qwen3":
        cfg.update(hidden_size=128, intermediate_size=256,
                   num_attention_heads=4, num_key_value_heads=2,
                   head_dim=32, vocab_size=512)
    else:
        cfg.update(d_model=64, vocab_size=16384)
        cfg["ssm_cfg"] = dict(cfg["ssm_cfg"], d_state=16, headdim=32)
    cell.config = cfg
    return cell


def run_tiny(name: str, *, seed: int = 1234567890123, seconds: float = 4.0,
             trace: bool = False, make_session=None, trace_dir=None,
             bench_path=None, base=None):
    """One run of the tiny cell on the CPU (the kernels as their jnp
    reference, 4 KiB chunks so that the pack and the scatter engage);
    returns (result line, the run)."""
    from chipbench import bench, peaks

    run = bench.Run(tiny_cell(name, bench_path, base), seed, seconds, trace,
                    t_start=time.perf_counter(), make_session=make_session,
                    trace_dir=trace_dir, chunk_bytes=1 << 12,
                    peak=peaks.PEAKS["TPU v5 lite"])
    return run.execute(), run
