"""The user code of a training session: its state layout and its training
step (the ``train`` op's cell; other ops are ``ops/<name>.py``).

The benchmark owns this code, so that the reference can run the very same
cells with no checkpointing and compare bit for bit: the system under test
is the session that commits and checks out, not the model.

Namespace layout (flat names, one leaf per tensor):

  state/params/<path>       parameters; ``state/params/lm_head`` is the same
                            array object as ``state/params/embed`` (tied)
  state/opt/mu/<path>       AdamW first moments (float32)
  state/opt/nu/<path>       AdamW second moments (float32)
  state/opt/count           AdamW step count (int32 device scalar)
  state/step                steps taken (python int)
  hparams/lr                learning rate (python float)
  data/seed                 the token stream's seed (python int)
  metrics/last_loss         the loss of the last step (python float)
"""
from __future__ import annotations

import functools
import importlib.util
import json
from pathlib import Path
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np

CONFIG_DIR = Path(__file__).resolve().parent / "configs"

LR = 3e-4
B1, B2, EPS, WD, CLIP = 0.9, 0.95, 1e-8, 0.1, 1.0


def load_architecture(name: str, config_dir: Path = CONFIG_DIR):
    """The plain reference module ``configs/<name>.py``."""
    path = config_dir / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_arch_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def flatten(tree: Dict, prefix: str) -> Dict[str, Any]:
    out = {}
    for k in sorted(tree):
        v = tree[k]
        name = f"{prefix}/{k}"
        if isinstance(v, dict):
            out.update(flatten(v, name))
        else:
            out[name] = v
    return out


def unflatten(flat: Dict[str, Any], prefix: str) -> Dict:
    root: Dict = {}
    pre = prefix + "/"
    for name, v in flat.items():
        if not name.startswith(pre):
            continue
        parts = name[len(pre):].split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return root


def _adamw(params, grads, mu, nu, count, lr):
    count = count + 1
    leaves = jax.tree.leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                         for g in leaves))
    clip = jnp.minimum(1.0, CLIP / (gnorm + 1e-9))
    c = count.astype(jnp.float32)

    def upd(p, g, m, v):
        g32 = g.astype(jnp.float32) * clip
        m = m * B1 + g32 * (1 - B1)
        v = v * B2 + jnp.square(g32) * (1 - B2)
        step = (m / (1 - B1 ** c)) / (jnp.sqrt(v / (1 - B2 ** c)) + EPS)
        p32 = p.astype(jnp.float32)
        if p.ndim >= 2:
            p32 = p32 * (1 - lr * WD)
        return (p32 - lr * step).astype(p.dtype), m, v

    out = jax.tree.map(upd, params, grads, mu, nu)
    is_t = lambda t: isinstance(t, tuple)
    pick = lambda i: jax.tree.map(lambda t: t[i], out, is_leaf=is_t)
    return pick(0), pick(1), pick(2), count


class Cells:
    """The model of one configuration: its initial state and its training
    step, as pure functions of device arrays (jitted once per shape), and
    the training cell that runs the step on a session's namespace.  ``Cells.of`` keeps one per configuration in a process, so
    that runs after the first reuse its compiled functions."""

    _made: Dict[str, "Cells"] = {}

    @classmethod
    def of(cls, cfg: dict, config_dir: Path = CONFIG_DIR) -> "Cells":
        key = json.dumps([cfg, str(config_dir)], sort_keys=True)
        if key not in cls._made:
            cls._made[key] = cls(cfg, config_dir)
        return cls._made[key]

    def __init__(self, cfg: dict, config_dir: Path = CONFIG_DIR):
        self.cfg = cfg
        self.arch = load_architecture(cfg["architecture"], config_dir)
        loss = functools.partial(self.arch.loss, cfg=cfg)

        def init(key):
            params = self.arch.init_params(cfg, key)
            zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
            return (params, jax.tree.map(zeros, params),
                    jax.tree.map(zeros, params), jnp.zeros((), jnp.int32))

        def train(params, mu, nu, count, tokens, lr):
            val, grads = jax.value_and_grad(loss)(params, tokens)
            p, m, v, c = _adamw(params, grads, mu, nu, count, lr)
            return p, m, v, c, val

        self._init = jax.jit(init)
        self._train = jax.jit(train)

    # ---- state <-> namespace -------------------------------------------
    def initial_state(self, seed: int) -> Dict[str, Any]:
        params, mu, nu, count = self._init(jax.random.key(seed))
        state = flatten(params, "state/params")
        state.update(flatten(mu, "state/opt/mu"))
        state.update(flatten(nu, "state/opt/nu"))
        state["state/params/lm_head"] = state["state/params/embed"]
        state["state/opt/count"] = count
        state["state/step"] = 0
        state["hparams/lr"] = LR
        state["data/seed"] = seed
        state["metrics/last_loss"] = float("nan")
        return state

    @staticmethod
    def _trees(ns):
        flat = {n: ns[n] for n in ns.keys() if n.startswith("state/")}
        flat.pop("state/params/lm_head", None)
        return (unflatten(flat, "state/params"), unflatten(flat, "state/opt/mu"),
                unflatten(flat, "state/opt/nu"))

    # ---- the cells --------------------------------------------------------
    def tokens(self, data_seed: int, batch: int, n_seq: int, seq: int):
        """Token ids of batch number ``batch`` of the stream, drawn from the
        vocabulary held here."""
        rng = np.random.default_rng([data_seed, batch])
        return rng.integers(0, self.cfg["vocab_size"], (n_seq, seq + 1),
                            dtype=np.int32)

    def train_cell(self, ns, batch: int, lr_scale: float, n_seq: int,
                   seq: int) -> None:
        """One AdamW step on batch ``batch`` at ``lr_scale`` times the
        configured rate."""
        ns["hparams/lr"] = LR * float(lr_scale)
        params, mu, nu = self._trees(ns)
        toks = jnp.asarray(self.tokens(ns["data/seed"], batch, n_seq, seq))
        p, m, v, c, val = self._train(params, mu, nu, ns["state/opt/count"],
                                      toks, jnp.float32(ns["hparams/lr"]))
        for name, x in flatten(p, "state/params").items():
            ns[name] = x
        ns["state/params/lm_head"] = ns["state/params/embed"]
        for name, x in flatten(m, "state/opt/mu").items():
            ns[name] = x
        for name, x in flatten(v, "state/opt/nu").items():
            ns[name] = x
        ns["state/opt/count"] = c
        ns["state/step"] = ns["state/step"] + 1
        ns["metrics/last_loss"] = float(val)   # waits for the step
