"""The one generator of traffic: it reads a mix's parameters from
``traffic/<name>.json`` and turns them, with the seed, into the cells and
checkouts of a run.

A mix is a closed loop of one user: each operation starts when the one
before it has ended.  Its file gives:

  setup     ``train_steps``: AdamW steps on the state before the attach;
            ``edits``: cells committed after the attach, so that the
            history has depth before the window
  cell      the cell each commit runs: ``{"op": <name>, ...}``, the op's
            own parameters beside its name.  An op is ``ops/<name>.py``,
            found by name: a ``CellOp`` subclass ``Op`` that gives each
            cell's arguments, the command that runs it, and the bytes and
            operations it changes and needs
  checkout  after every ``every`` cells, a checkout of the commit d back
            along the current branch, d uniform in
            ``back_min..min(back_max, cells on the branch since the attach)``
  warmup    set-up work that compiles what the window runs: ``cells``
            cells of the window's op on data the window never uses, and a
            walk of checkouts along the set-up's branch, one over each
            distance d in ``checkouts``; the window then starts where the
            set-up left the branch
  readback  commits the check reads back after the window (the newest one
            and others drawn from the seed)
"""
from __future__ import annotations

import importlib.util
import sys
from pathlib import Path
from typing import Callable, Dict, Iterator, List, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent

# batch numbers of set-up steps lie apart from those of cells
SETUP_BATCH0 = 1 << 30


class CellOp:
    """What one kind of cell does.  ``ops/<name>.py`` defines ``Op``, a
    subclass; the defaults here suit an op that changes no array."""

    def __init__(self, cell: dict, cfg: dict, rng: np.random.Generator):
        self.cell = cell          # the mix's ``cell`` entry
        self.cfg = cfg
        self.rng = rng            # the traffic's own, drawn in a fixed order

    def args(self, k: int) -> dict:
        """Arguments of the ``k``-th cell of the run."""
        raise NotImplementedError

    def warmup_args(self, i: int) -> dict:
        """Arguments of the ``i``-th warm-up cell, on data no cell uses."""
        raise NotImplementedError

    def command(self, cells) -> Callable:
        """The command the session registers: ``fn(ns, **args)``."""
        raise NotImplementedError

    def prepare(self, sizes: Dict[str, int], shapes: Dict[str, tuple],
                chunk_bytes: int) -> int:
        """Called once the state is built; returns the least number of
        cells the set-up commits after the attach."""
        return 0

    def on_checkout(self) -> None:
        """Told of every checkout the mix makes."""

    def changed_bytes(self, sizes: Dict[str, int], shapes: Dict[str, tuple],
                      args: dict) -> int:
        """Bytes of the state one cell changes, from the op's definition."""
        return 0

    def flops(self, cells, args: dict) -> float:
        """Model operations one cell needs."""
        return 0.0


_ops: Dict[str, type] = {}


def load_op(name: str, base: Path = HERE) -> type:
    """``Op`` of ``base/ops/<name>.py``, loaded once per process."""
    path = base / "ops" / f"{name}.py"
    key = str(path)
    if key not in _ops:
        if not path.is_file():
            raise ValueError(f"unknown cell op {name!r}: no {path}")
        spec = importlib.util.spec_from_file_location(
            f"chipbench_op_{name}_{len(_ops)}", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = mod
        spec.loader.exec_module(mod)
        _ops[key] = mod.Op
    return _ops[key]


class Traffic:
    def __init__(self, spec: dict, cfg: dict, seed: int, base: Path = HERE):
        self.spec = spec
        self.cfg = cfg
        self.rng = np.random.default_rng([seed, 0x7AFF1C])
        self.cell = spec["cell"]
        self.command = self.cell["op"]
        self.op = load_op(self.command, base)(self.cell, cfg, self.rng)
        self.n_cells = 0
        self.n_setup = 0         # cells the op needs in set-up

    # ---- set-up -------------------------------------------------------------
    def setup_train_steps(self) -> List[dict]:
        """Arguments of the AdamW steps taken before the attach."""
        c = self.spec["cell"]
        batch, seq = c.get("batch", 8), c.get("seq", 64)
        return [{"batch": SETUP_BATCH0 + i, "lr_scale": 1.0, "n_seq": batch,
                 "seq": seq}
                for i in range(self.spec["setup"]["train_steps"])]

    def prepare(self, sizes: Dict[str, int], shapes: Dict[str, tuple],
                chunk_bytes: int) -> None:
        self.n_setup = self.op.prepare(sizes, shapes, chunk_bytes)

    def setup_edits(self) -> int:
        return max(int(self.spec["setup"].get("edits", 0)), self.n_setup)

    # ---- cells and checkouts ----------------------------------------------
    def next_cell(self) -> Tuple[str, dict]:
        """The command and arguments of the next cell."""
        k = self.n_cells
        self.n_cells += 1
        return self.command, self.op.args(k)

    def warmup_cell(self, i: int) -> Tuple[str, dict]:
        """A cell of the set-up's warm-up: the window's op on data the
        window never uses."""
        return self.command, self.op.warmup_args(i)

    def checkout_every(self) -> int:
        return int(self.spec["checkout"]["every"])

    def checkout_distance(self, depth: int) -> int:
        """How far back along the branch the next checkout goes, given the
        cells on the branch since the attach; 0 when none can."""
        co = self.spec["checkout"]
        hi = min(co["back_max"], depth)
        lo = co["back_min"]
        self.op.on_checkout()
        if hi < lo:
            return 0
        return int(self.rng.integers(lo, hi + 1))

    def readback_sample(self, commits: List[str], newest: str) -> List[str]:
        """Commits to read back after the window: the newest and others
        drawn from the seed."""
        others = [c for c in commits if c != newest]
        k = min(len(others), max(0, int(self.spec["readback"]) - 1))
        pick = self.rng.choice(len(others), size=k, replace=False) \
            if k else []
        return [newest] + [others[i] for i in sorted(pick)]

    def ops(self) -> Iterator[Tuple[str, dict]]:
        """The window's operations, endless: ``("cell", (command, args))``
        and ``("checkout", None)`` in the mix's pattern."""
        while True:
            for _ in range(self.checkout_every()):
                yield "cell", self.next_cell()
            yield "checkout", None
