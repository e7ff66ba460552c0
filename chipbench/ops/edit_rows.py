"""The ``edit_rows`` op: re-initialize one block of ``rows`` rows of the
table ``leaf`` (fresh parameters from the data seed and the block, zeroed
AdamW moments), then a commit.  The blocks come in a seeded order of the
table's token rows, none repeated within a run.

``prepare`` puts first in that order a few blocks that between them touch
the edited leaves in every way any block does, as the program's device
pass reads them: chunks of the session's size in segments of
``repro.kernels.delta_pack.ops.DEFAULT_SEG_BYTES``, each segment handled
apart, with shapes that follow its count of dirty chunks.  The set-up's
edits begin with those blocks, so that their shapes compile there.
"""
import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import CellOp


@functools.partial(jax.jit, static_argnums=5)
def edit(table, mu, nu, row0, key, rows):
    width = table.shape[1]
    new = (jax.random.normal(key, (rows, width), jnp.float32)
           * 0.02).astype(table.dtype)
    zero = jnp.zeros((rows, width), mu.dtype)
    at = (row0, 0)
    return (jax.lax.dynamic_update_slice(table, new, at),
            jax.lax.dynamic_update_slice(mu, zero, at),
            jax.lax.dynamic_update_slice(nu, zero, at))


def edit_cell(ns, leaf: str, block: int, rows: int) -> None:
    """Re-initialize rows [block*rows, (block+1)*rows) of a parameter table:
    fresh values from the data seed and the block, zeroed moments."""
    names = leaves(leaf)
    key = jax.random.fold_in(jax.random.key(ns["data/seed"]), block)
    out = edit(*(ns[n] for n in names), jnp.int32(block * rows), key, rows)
    for n, x in zip(names, out):
        ns[n] = x
    if leaf == "embed":
        ns["state/params/lm_head"] = ns["state/params/embed"]
    out[0].block_until_ready()


def leaves(leaf: str) -> List[str]:
    """The leaves an edit of table ``leaf`` changes: the table and both of
    its moments."""
    return [f"state/params/{leaf}", f"state/opt/mu/{leaf}",
            f"state/opt/nu/{leaf}"]


class Op(CellOp):
    def __init__(self, cell, cfg, rng):
        super().__init__(cell, cfg, rng)
        self.blocks = rng.permutation(cfg["vocab_size"] // cell["rows"])

    def args(self, k):
        block = int(self.blocks[k % len(self.blocks)])
        return {"leaf": self.cell["leaf"], "block": block,
                "rows": self.cell["rows"]}

    def warmup_args(self, i):
        return {"leaf": self.cell["leaf"], "block": int(self.blocks[-1 - i]),
                "rows": self.cell["rows"]}

    def command(self, cells):
        return edit_cell

    def changed_bytes(self, sizes, shapes, args):
        return sum(sizes[n] // shapes[n][0] * args["rows"]
                   for n in leaves(args["leaf"]))

    def prepare(self, sizes, shapes, chunk_bytes):
        from repro.kernels.delta_pack.ops import DEFAULT_SEG_BYTES

        return len(self.cover_first(
            [(shapes[n][0], sizes[n] // shapes[n][0])
             for n in leaves(self.cell["leaf"])],
            chunk_bytes, DEFAULT_SEG_BYTES))

    def cover_first(self, leaf_rows: List[Tuple[int, int]], chunk_bytes: int,
                    seg_bytes: int) -> List[int]:
        """Put first in the order of blocks a few that between them show
        every pattern of dirty chunks that any block shows.

        ``leaf_rows`` gives (rows, bytes per row) of each leaf an edit
        changes; a block's pattern is the set of (leaf, last segment or
        not, dirty chunks in the segment) it touches.  The blocks are
        picked greedily, each adding the most patterns not yet shown."""
        rows = self.cell["rows"]
        seg_chunks = max(1, seg_bytes // chunk_bytes)

        def pattern(block: int) -> frozenset:
            out = set()
            for li, (n_rows, row_bytes) in enumerate(leaf_rows):
                n_chunks = -(-n_rows * row_bytes // chunk_bytes)
                last = (n_chunks - 1) // seg_chunks
                c0 = block * rows * row_bytes // chunk_bytes
                c1 = ((block + 1) * rows * row_bytes - 1) // chunk_bytes
                per_seg: dict = {}
                for c in range(c0, c1 + 1):
                    s = c // seg_chunks
                    per_seg[s] = per_seg.get(s, 0) + 1
                for s, n in per_seg.items():
                    out.add((li, s == last, n))
            return frozenset(out)

        order = [int(b) for b in self.blocks]
        pats = [pattern(b) for b in order]
        todo = set().union(*pats)
        chosen: List[int] = []
        while todo:
            i = max(range(len(order)), key=lambda j: len(pats[j] & todo))
            chosen.append(i)
            todo -= pats[i]
        picked = [order[i] for i in chosen]
        rest = [b for i, b in enumerate(order) if i not in chosen]
        self.blocks = np.asarray(picked + rest)
        return picked
