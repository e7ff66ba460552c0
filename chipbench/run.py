#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

One process, one run: it finds a TPU (or exits non-zero without a result),
keeps JAX's compilation cache in ``<checkout>/.jax_cache``, builds the
cell's state on the device from the seed, opens a ``dir://`` store in a
fresh temporary directory, warms up every shape the window uses, measures
for ``--seconds``, checks what the window restored and wrote against the
plain reference, deletes the store, and prints one JSON object as the last
line of standard output.  With ``--trace 0`` it carries the cell's
end-to-end metrics; with ``--trace 1``, its per-layer metrics, read from the
session's spans and a profiler trace of the window.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the cache lives inside the checkout, at a path that never moves; JAX
# writes nothing to a cache directory that does not exist yet
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.makedirs(ROOT / ".jax_cache", exist_ok=True)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default="",
                    help="directory to keep the profiler trace in")
    return ap.parse_args(argv)


def _terminated(signum, _frame):
    # unwinds through the run's ``finally`` blocks, which delete the store
    # and the trace; no result line is printed
    raise SystemExit(128 + signum)


def main(argv=None) -> int:
    args = parse(argv)
    signal.signal(signal.SIGTERM, _terminated)
    from chipbench import bench

    cell = bench.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    try:
        devices = bench.find_chips(cell.entry["chips"])
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cache = use_compile_cache()
    bench.log(f"device: {devices[0].device_kind} x {len(devices)}; "
              f"compile cache {cache}")
    trace_dir = None
    if args.trace:
        trace_dir = args.keep_trace or tempfile.mkdtemp(
            prefix="chipbench-trace-")
    run = bench.Run(cell, args.seed, args.seconds, bool(args.trace),
                    t_start=T_START, devices=devices, trace_dir=trace_dir)
    try:
        out = run.execute()
    finally:
        if trace_dir and not args.keep_trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
