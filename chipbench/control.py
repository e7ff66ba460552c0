#!/usr/bin/env python3
"""The control of the check: the plain reference put in the session's
place, one precision down, must come out as not correct.

    python3 chipbench/control.py --workload <cell> --seeds 1,2,3 \\
        --seconds <s>

``ControlSession`` is a checkpointer with nothing of the system under test
in it: each commit keeps the arrays the cell changed on the host, float32
rounded to bfloat16 and bfloat16 to float8 (e4m3), the step a lossy
checkpoint codec would tempt one to take; a checkout puts them back in
their own dtype.  The runs drive it through the benchmark's own window and
check, and print one JSON line per seed with the numbers compared.  The
benchmark's runs never run it.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Any, Callable, Dict  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# the cache lives inside the checkout, at a path that never moves; JAX
# writes nothing to a cache directory that does not exist yet
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.makedirs(ROOT / ".jax_cache", exist_ok=True)


class ControlSession:
    """Full copies on the host, one precision down."""

    def __init__(self, store_dir: str, commands: Dict[str, Callable], *,
                 trace: bool = False):
        import jax.numpy as jnp

        self.commands = commands
        self.ns: Dict[str, Any] = {}
        self.commits: Dict[str, Dict[str, tuple]] = {}
        self.head = ""
        self.bytes = 0
        self._last: Dict[str, tuple] = {}
        self.lower = {jnp.dtype(jnp.float32): jnp.bfloat16,
                      jnp.dtype(jnp.bfloat16): jnp.float8_e4m3fn}

    def _commit(self) -> str:
        import jax
        import numpy as np

        snap: Dict[str, tuple] = {}
        made: Dict[int, tuple] = {}     # one copy per array object (ties)
        for name, v in self.ns.items():
            prev = self._last.get(name)
            if prev is not None and prev[0] is v:
                entry = prev[1]
            elif id(v) in made:
                entry = made[id(v)]
            elif isinstance(v, jax.Array):
                low = self.lower.get(v.dtype)
                host = np.asarray(v.astype(low) if low is not None else v)
                self.bytes += host.nbytes
                entry = ("array", host, v.dtype)
            else:
                entry = ("value", copy.deepcopy(v))
            made.setdefault(id(v), entry)
            snap[name] = entry
        self._last = {n: (self.ns[n], snap[n]) for n in self.ns}
        cid = f"k{len(self.commits):05d}"
        self.commits[cid] = snap
        self.head = cid
        return cid

    def attach(self, state: Dict[str, Any]) -> str:
        self.ns = dict(state)
        self._last = {}
        return self._commit()

    def run(self, command: str, args: dict) -> str:
        self.commands[command](self.ns, **args)
        return self._commit()

    def checkout(self, commit: str) -> None:
        import jax.numpy as jnp

        snap = self.commits[commit]
        made: Dict[int, Any] = {}
        ns = {}
        for name, e in snap.items():
            if e[0] == "array":
                if id(e) not in made:
                    made[id(e)] = jnp.asarray(e[1]).astype(e[2])
                ns[name] = made[id(e)]
            else:
                ns[name] = copy.deepcopy(e[1])
        self.ns = ns
        self.head = commit
        self._last = {n: (ns[n], snap[n]) for n in ns}

    def flush(self) -> None:
        pass

    def stored_bytes(self) -> int:
        return self.bytes

    def spans(self):
        return []

    def span_epoch(self) -> float:
        return 0.0

    def clear_spans(self) -> None:
        pass

    def close(self) -> None:
        self.commits.clear()
        self.ns = {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, run one after another")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from chipbench import bench

    cell = bench.load_cell(args.workload)
    import jax

    from repro.launch.compile_cache import use_compile_cache

    try:
        devices = bench.find_chips(cell.entry["chips"])
    except bench.NoChip as e:
        print(f"chipbench control: {e}", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    use_compile_cache()
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench.Run(cell, seed, args.seconds, False, t_start=t_start,
                        devices=devices,
                        make_session=lambda d, cmds, trace: ControlSession(
                            d, cmds, trace=trace))
        out = run.execute()
        print(json.dumps({"control": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
        del run
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
