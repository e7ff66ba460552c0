"""The plain reference: the same cells on the same data with no
checkpointing, from the seed.

The state of any commit is rebuilt by running, from the attach state, the
cells on its path through the history, on a plain dict.  Nothing of the
system under test is imported or reused: the cells are the benchmark's own
code, and the attach state is made again from the seed.
"""
from __future__ import annotations

from typing import Dict, Iterable

from chipbench import digest


class Reference:
    def __init__(self, cells, traffic, data_seed: int, history):
        self.cells = cells
        self.traffic = traffic
        self.data_seed = data_seed
        self.history = history

    def attach_state(self) -> Dict:
        state = self.cells.initial_state(self.data_seed)
        for args in self.traffic.setup_train_steps():
            self.cells.train_cell(state, **args)
        return state

    def fingerprints(self, commits: Iterable[str]) -> Dict[str, dict]:
        base = self.attach_state()
        run = {self.traffic.command: self.traffic.op.command(self.cells)}
        out = {}
        for c in sorted(commits):
            ns = dict(base)
            for step in self.history.path(c):
                command, args = self.history.cell[step]
                run[command](ns, **args)
            out[c] = digest.fingerprint(ns)
            del ns
        return out
