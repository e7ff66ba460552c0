"""One run of one cell: set-up, the measured window, the trace reduction,
the check against the reference, and the result line.

Everything of a cell is found by name: the cell's entry in
``BENCHMARK.json``, its configuration ``configs/<config>.json`` with the
plain reference ``configs/<architecture>.py``, its mix
``traffic/<traffic>.json``, and one reader ``metrics/<metric>.py`` for each
per-layer metric.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CHUNK_BYTES = 1 << 16          # the training session's own chunk size


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell by name
# ---------------------------------------------------------------------------

@dataclass
class Cell:
    name: str
    entry: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    base: Path = HERE            # holds configs/, traffic/ and metrics/


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: Optional[Path] = None,
              base: Path = HERE) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration and
    mix read from ``base/configs/<config>.json`` and
    ``base/traffic/<traffic>.json``."""
    bench = json.loads((bench_path or ROOT / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    config = json.loads((base / "configs" / f"{entry['config']}.json")
                        .read_text())
    traffic = json.loads((base / "traffic" / f"{entry['traffic']}.json")
                         .read_text())
    return Cell(name=name, entry=entry, config=config, traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                base=base)


def quantity(metric: str, known: Callable[[str], bool]) -> Optional[str]:
    """What a metric reports: its own name, or the name less trailing
    ``.<tag>`` parts, the first that ``known`` accepts.  A tag splits one
    quantity into metrics of their own, with bounds of their own, for cells
    that differ in noise or in what the quantity moves (``commit_s.sparse``
    and ``commit_s.rollback`` are both ``commit_s``)."""
    name = metric
    while not known(name):
        if "." not in name:
            return None
        name = name.rsplit(".", 1)[0]
    return name


def reader_path(metric: str, base: Path = HERE) -> Optional[Path]:
    """``base/metrics/<name>.py`` of the metric's quantity."""
    name = quantity(metric,
                    lambda n: (base / "metrics" / f"{n}.py").is_file())
    return None if name is None else base / "metrics" / f"{name}.py"


def load_reader(metric: str, base: Path = HERE) -> Callable:
    """``read`` of the metric's reader (``reader_path``)."""
    path = reader_path(metric, base)
    if path is None:
        raise FileNotFoundError(f"no reader for {metric!r} under "
                                f"{base / 'metrics'}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def find_chips(chips: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX found no devices: {e}") from None
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is "
                     f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


class CompileWatch:
    """Compilations of this process, from JAX's monitoring events: backend
    compiles and loads from the persistent cache."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = self.cache_hits = 0
        self.compile_s = 0.0
        self.lowered: List[str] = []     # every function lowered, in order
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def _duration(self, event: str, secs: float, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered.append(str(kw.get("fun_name", "?")))

    def count(self) -> int:
        return self.compiles + self.cache_hits


def dir_bytes(path: str) -> int:
    tot = 0
    for dirpath, _dirs, files in os.walk(path):
        for f in files:
            try:
                tot += os.stat(os.path.join(dirpath, f)).st_size
            except FileNotFoundError:
                pass
    return tot


# ---------------------------------------------------------------------------
# the session under test, and what the window records
# ---------------------------------------------------------------------------

class ProgramSession:
    """The system under test: a ``KishuSession`` on a ``dir://`` store with
    no store codec and 64 KiB chunks."""

    def __init__(self, store_dir: str, commands: Dict[str, Callable], *,
                 trace: bool, chunk_bytes: int = CHUNK_BYTES):
        from repro.core import KishuSession
        from repro.core.chunkstore import open_store

        self.store_dir = store_dir
        self.kishu = KishuSession(open_store(f"dir://{store_dir}"),
                                  chunk_bytes=chunk_bytes, trace=trace)
        for name, fn in commands.items():
            self.kishu.register(name, fn)

    @property
    def ns(self):
        return self.kishu.ns

    @property
    def head(self) -> str:
        return self.kishu.head

    def attach(self, state: Dict[str, Any]) -> str:
        return self.kishu.init_state(state)

    def run(self, command: str, args: dict) -> str:
        return self.kishu.run(command, **args)

    def checkout(self, commit: str) -> None:
        self.kishu.checkout(commit)

    def flush(self) -> None:
        self.kishu.writer.flush()
        self.kishu.engine.flush()

    def stored_bytes(self) -> int:
        return dir_bytes(self.store_dir)

    def spans(self) -> List[dict]:
        return self.kishu.obs.tracer.to_doc()

    def span_epoch(self) -> float:
        return self.kishu.obs.tracer.epoch

    def clear_spans(self) -> None:
        self.kishu.obs.tracer.clear()

    def close(self) -> None:
        self.kishu.close()


@dataclass
class Op:
    kind: str                    # "commit" | "checkout"
    seconds: float
    t0: float                    # time.monotonic() at the start
    t1: float
    commit: str                  # the commit made, or checked out
    undone: List[str] = field(default_factory=list)   # checkouts
    fingerprint: Optional[dict] = None


@dataclass
class Window:
    ops: List[Op] = field(default_factory=list)
    flush_s: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    failed: int = 0
    errors: List[str] = field(default_factory=list)

    def of(self, kind: str) -> List[Op]:
        return [o for o in self.ops if o.kind == kind]


class History:
    """Every commit of the run: its parent and the cell that made it, so
    the reference can rebuild the state of any commit."""

    def __init__(self):
        self.parent: Dict[str, Optional[str]] = {}
        self.cell: Dict[str, Tuple[str, dict]] = {}
        self.attach = ""

    def add(self, commit: str, parent: Optional[str], command: str,
            args: dict) -> None:
        self.parent[commit] = parent
        self.cell[commit] = (command, dict(args))

    def path(self, commit: str) -> List[str]:
        """Commits from just after the attach down to ``commit``."""
        out = []
        c = commit
        while c != self.attach:
            out.append(c)
            c = self.parent[c]
        return out[::-1]


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def _seed31(seed: int) -> int:
    import numpy as np

    return int(np.random.default_rng([seed, 31]).integers(1, 2**31 - 1))


def leaf_bytes(state: Dict[str, Any]) -> Dict[str, int]:
    """Bytes of every array of the state (the tied alias once)."""
    import numpy as np

    return {n: int(x.size) * np.dtype(x.dtype).itemsize
            for n, x in state.items()
            if hasattr(x, "dtype") and n != "state/params/lm_head"}


class Run:
    """One run of one cell; ``execute`` returns the result line's object."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool, *,
                 t_start: float, devices=None, make_session=None,
                 trace_dir: Optional[str] = None,
                 chunk_bytes: int = CHUNK_BYTES, peak=None):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.t_start = t_start
        self.devices = devices
        self.chunk_bytes = chunk_bytes
        self.peak = peak             # None: from the device kind
        self.make_session = make_session or (
            lambda d, cmds, trace: ProgramSession(
                d, cmds, trace=trace, chunk_bytes=chunk_bytes))
        self.trace_dir = trace_dir
        self.window = Window()
        self.history = History()

    # ---- set-up -------------------------------------------------------------
    def _setup(self):
        from chipbench import digest
        from chipbench.cells import Cells
        from chipbench.traffic import Traffic

        cfg = self.cell.config
        self.cells = Cells.of(cfg, self.cell.base / "configs")
        self.traffic = Traffic(self.cell.traffic, cfg, self.seed,
                               self.cell.base)
        self.data_seed = _seed31(self.seed)
        state = self.cells.initial_state(self.data_seed)
        for args in self.traffic.setup_train_steps():
            self.cells.train_cell(state, **args)
        self.sizes = leaf_bytes(state)
        self.shapes = {n: tuple(state[n].shape) for n in self.sizes}
        self.traffic.prepare(self.sizes, self.shapes, self.chunk_bytes)
        self.store_dir = tempfile.mkdtemp(prefix="chipbench-store-")
        self.sess = self.make_session(self.store_dir, self.commands(),
                                      self.trace)
        self.history.attach = self.sess.attach(state)
        del state
        self.branch: List[str] = []         # commits since the attach
        for _ in range(self.traffic.setup_edits()):
            self._cell(*self.traffic.next_cell(), timed=False)
        # warm-up: the window's shapes, then back to where the window starts
        warm = self.cell.traffic.get("warmup", {})
        tip = list(self.branch)
        for i in range(int(warm.get("cells", 0))):
            self._cell(*self.traffic.warmup_cell(i), timed=False)
        # checkouts over each distance d: a walk along the branch, longest
        # step first, each step d back where it can go and else d forward
        line = [self.history.attach] + self.branch
        at = len(line) - 1
        for d in sorted({min(int(d), at) for d in warm.get("checkouts", [])
                         if int(d) > 0}, reverse=True):
            if at - d >= 0:
                at -= d
            elif at + d < len(line):
                at += d
            else:
                continue
            self._checkout_to(line[at], timed=False)
            digest.fingerprint(self.sess.ns)
        if at != len(line) - 1 or self.branch != tip:
            target = tip[-1] if tip else self.history.attach
            self._checkout_to(target, timed=False)
            self.branch = tip
        digest.fingerprint(self.sess.ns)

    def commands(self) -> Dict[str, Callable]:
        t = self.traffic
        return {t.command: t.op.command(self.cells)}

    def _back(self, d: int) -> str:
        return self.branch[-1 - d] if d < len(self.branch) \
            else self.history.attach

    def _checkout_to(self, commit: str, *, timed: bool) -> Optional[Op]:
        import jax

        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("chipbench.checkout"):
            self.sess.checkout(commit)
            # a checkout is done when the restored state is on the device:
            # the program may return while its uploads and scatters run
            jax.block_until_ready([v for v in self.sess.ns.values()
                                   if isinstance(v, jax.Array)])
        t1 = time.monotonic()
        return Op("checkout", t1 - t0, t0, t1, commit) if timed else None

    def _cell(self, command: str, args: dict, *,
              timed: bool) -> Optional[Op]:
        import jax

        parent = self.sess.head
        t0 = time.monotonic()
        with jax.profiler.TraceAnnotation("chipbench.commit"):
            commit = self.sess.run(command, args)
        t1 = time.monotonic()
        self.history.add(commit, parent, command, args)
        self.branch.append(commit)
        return Op("commit", t1 - t0, t0, t1, commit) if timed else None

    # ---- the window ---------------------------------------------------------
    def _measure(self, watch: CompileWatch):
        import jax

        from chipbench import digest

        w = self.window
        before_compiles = watch.count()
        before_lowered = len(watch.lowered)
        self.stored0 = self.sess.stored_bytes()
        if self.trace:
            self.sess.clear_spans()
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        ops = self.traffic.ops()
        w.t0 = time.monotonic()
        deadline = w.t0 + self.seconds
        with jax.profiler.TraceAnnotation("chipbench.window"):
            while time.monotonic() < deadline:
                kind, what = next(ops)
                try:
                    if kind == "cell":
                        w.ops.append(self._cell(*what, timed=True))
                        continue
                    d = self.traffic.checkout_distance(len(self.branch))
                    if d <= 0:
                        continue
                    op = self._checkout_to(self._back(d), timed=True)
                    op.undone = self.branch[len(self.branch) - d:]
                    self.branch = self.branch[:len(self.branch) - d]
                    w.ops.append(op)
                    op.fingerprint = digest.fingerprint(self.sess.ns)
                except Exception:  # noqa: BLE001 — counted, then stop
                    w.failed += 1
                    w.errors.append(traceback.format_exc())
                    break
            t = time.monotonic()
            with jax.profiler.TraceAnnotation("chipbench.flush"):
                self.sess.flush()
            w.t1 = time.monotonic()
            w.flush_s = w.t1 - t
        if self.trace:
            jax.profiler.stop_trace()
        self.window_compiles = watch.count() - before_compiles
        if self.window_compiles:
            log(f"compiled in the window: "
                f"{watch.lowered[before_lowered:]}")
        self.stored1 = self.sess.stored_bytes()

    # ---- what the traffic knows of its own work ---------------------------
    def cell_dirty_bytes(self, commit: str) -> int:
        _command, args = self.history.cell[commit]
        return self.traffic.op.changed_bytes(self.sizes, self.shapes, args)

    def cell_flops(self, commit: str) -> float:
        _command, args = self.history.cell[commit]
        return self.traffic.op.flops(self.cells, args)

    def checkout_bytes(self, op: Op) -> int:
        """Bytes in which the state before a checkout and its target
        differ: what the undone cells changed, at most the whole state."""
        return min(sum(self.sizes.values()),
                   sum(self.cell_dirty_bytes(c) for c in op.undone))

    # ---- metrics ------------------------------------------------------------
    def end_to_end(self) -> Dict[str, float]:
        from chipbench import stats

        w = self.window
        commits = [o.seconds for o in w.of("commit")]
        checkouts = [o.seconds for o in w.of("checkout")]
        out: Dict[str, Optional[float]] = {
            "setup_s": self.setup_s,
            "commit_s": stats.per_op(sum(commits) + w.flush_s, len(commits)),
            "checkout_s": stats.per_op(sum(checkouts), len(checkouts)),
            "stored_MB_per_commit": stats.per_op(
                stats.megabytes(self.stored1 - self.stored0), len(commits)),
        }
        return {k: v for k, v in out.items() if v is not None}

    # ---- the check ----------------------------------------------------------
    def _readback(self) -> Dict[str, dict]:
        from chipbench import digest

        commits = [o.commit for o in self.window.of("commit")]
        if not commits:
            return {}
        got = {}
        for c in self.traffic.readback_sample(commits, commits[-1]):
            try:
                self.sess.checkout(c)
                got[c] = digest.fingerprint(self.sess.ns)
            except Exception:  # noqa: BLE001 — a read that fails differs
                got[c] = {"<error>": traceback.format_exc(limit=3)}
        return got

    def check(self, readback: Dict[str, dict]) -> Dict[str, dict]:
        from chipbench.reference import Reference

        ref = Reference(self.cells, self.traffic, self.data_seed,
                        self.history)
        want = ref.fingerprints(
            {o.commit for o in self.window.of("checkout")} | set(readback))
        bad_co = [o.commit for o in self.window.of("checkout")
                  if o.fingerprint != want[o.commit]]
        bad_rb = [c for c, fp in readback.items() if fp != want[c]]
        from chipbench import digest

        for c in bad_co + bad_rb:
            got = next((o.fingerprint for o in self.window.of("checkout")
                        if o.commit == c), None) or readback.get(c)
            log(f"commit {c}: differs from the reference in "
                f"{digest.differences(got, want[c])[:8]}")
        n_co = len(self.window.of("checkout"))
        return {
            "checkouts_differing": {"value": len(bad_co), "limit": 0,
                                    "of": n_co},
            "readbacks_differing": {"value": len(bad_rb), "limit": 0,
                                    "of": len(readback)},
            "operations_failed": {"value": self.window.failed, "limit": 0},
        }

    # ---- the whole run ------------------------------------------------------
    def execute(self) -> dict:
        import jax

        devices = self.devices or jax.devices()[:self.cell.entry["chips"]]
        watch = CompileWatch()
        try:
            self._setup()
            self.setup_s = time.perf_counter() - self.t_start
            log(f"set-up: {self.setup_s:.3f} s; history "
                f"{len(self.history.parent)} commits; compiles so far "
                f"{watch.compiles} ({watch.compile_s:.1f} s), "
                f"{watch.cache_hits} persistent-cache loads")
            self._measure(watch)
            print(f"window compiles: {self.window_compiles}", flush=True)
            peak = memory_peak_bytes(devices)
            e2e = self.end_to_end()
            per_layer = self.per_layer() if self.trace else {}
            readback = self._readback()
        finally:
            if hasattr(self, "sess"):
                self.sess.close()
                del self.sess
            if hasattr(self, "store_dir"):
                shutil.rmtree(self.store_dir, ignore_errors=True)
        gc.collect()
        checks = self.check(readback)
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        w = self.window
        for err in w.errors:
            log(err)
        names = [m["name"] for m in (self.cell.per_layer if self.trace
                                     else self.cell.end_to_end)]
        source = per_layer if self.trace else e2e
        units = {m["name"]: m["unit"] for m in
                 self.cell.per_layer + self.cell.end_to_end}
        if not self.trace:
            source = {n: source[q] for n in names
                      if (q := quantity(n, source.__contains__))}
        metrics = {n: {"value": source[n], "unit": units[n]}
                   for n in names if n in source}
        d0 = devices[0]
        out = {"correct": correct, "attempted": len(w.ops) + w.failed,
               "failed": w.failed, "metrics": metrics,
               "device": {"platform": d0.platform, "kind": d0.device_kind,
                          "count": len(devices), "memory_peak_bytes": peak}}
        if self.trace:
            out["device"].update(self.trace_device)
            out["breakdown"] = self.breakdown
        for name, c in checks.items():
            log(f"check {name}: {c['value']} (limit {c['limit']})")
        out["checks"] = checks
        return out

    def per_layer(self) -> Dict[str, float]:
        from chipbench import trace as trace_mod
        from chipbench.readers import Context

        path = trace_mod.find_xplane(self.trace_dir)
        tr = trace_mod.load(path) if path else trace_mod.Trace()
        ctx = Context(run=self, trace=tr, peak=self.peak)
        self.trace_device = ctx.device_summary()
        self.breakdown = ctx.breakdown()
        out = {}
        for m in self.cell.per_layer:
            v = load_reader(m["name"], self.cell.base)(ctx)
            if v is not None:
                out[m["name"]] = v
        return out
