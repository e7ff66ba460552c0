"""The ``train`` op: one AdamW step of the configuration's own training
step on batch ``k`` of the token stream, then a commit.  Every byte of the
parameters and moments changes.

Mix keys: ``batch`` sequences of ``seq`` tokens per step; ``lr_scales``,
the scales of the configured learning rate, moving on to the next at every
checkout, so that a branch made after a rollback never repeats old chunks.
"""
from chipbench import peaks
from chipbench.traffic import SETUP_BATCH0, CellOp


class Op(CellOp):
    def __init__(self, cell, cfg, rng):
        super().__init__(cell, cfg, rng)
        self.lr_index = 0

    def args(self, k):
        c = self.cell
        scales = c["lr_scales"]
        return {"batch": k, "n_seq": c["batch"], "seq": c["seq"],
                "lr_scale": scales[self.lr_index % len(scales)]}

    def warmup_args(self, i):
        c = self.cell
        return {"batch": SETUP_BATCH0 + 1000 + i, "n_seq": c["batch"],
                "seq": c["seq"], "lr_scale": c["lr_scales"][0]}

    def on_checkout(self):
        self.lr_index += 1

    def command(self, cells):
        return cells.train_cell

    def changed_bytes(self, sizes, shapes, args):
        return sum(sizes.values())

    def flops(self, cells, args):
        arch, cfg = cells.arch, cells.cfg
        return peaks.train_step_flops(
            {"matmul": arch.matmul_params(cfg)}, args["n_seq"] * args["seq"],
            arch.attn_flops_per_token(cfg, args["seq"]))
