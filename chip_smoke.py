#!/usr/bin/env python3
"""Smoke run of the training-session commit/checkout path on a TPU.

    python3 chip_smoke.py              # one chip: the main path
    python3 chip_smoke.py --chips 4    # four chips: the sharded train step

One chip.  ``ManagedTrainingSession`` (src/repro/train/loop.py), driven
through ``KishuSession.run`` / ``checkout`` as ``launch/train.py`` drives
it, on smollm-360m at its published widths (32 layers, d_model 960, vocab
49152; bf16 parameters, f32 AdamW moments, random weights from --seed),
with the session's own 64 KiB chunks.  The cells, in order:

  attach           every leaf is new: detection hashes run ``chunk_hash``
  train            a few steps, everything dirty: ``delta_pack`` runs
  sparse           zero the AdamW moments of one layer: a small,
                   compressible dirty set, so ``delta_pack`` compaction and
                   the ``delta_codec`` encode both run
  checkout_sparse  back across the sparse cell: the patch plan, through
                   ``patch_scatter``
  checkout_train   back across training: a full load
  checkout_codec   forward to the sparse cell: a full load that reads the
                   chunks the codec encoded on device

Before the cells, the 16- and 8-bit word packing (``_to_words``) of a
bf16 and an int8 device array must equal their host bytes as
little-endian 32-bit words.

Every restored leaf must equal, bit for bit, a host copy taken right after
the cell that produced it; the kernels must have run as Pallas kernels;
no kernel fallback may be counted; and the pack, the codec and the scatter
must each have served at least one co-variable.

Four chips (``--chips 4``, and nothing else).  A few steps of the sharded
train step (``ShardingRules`` + ``jit`` with in/out shardings) on a
(data, model) mesh, against the same steps on one chip; then a commit and
a checkout of the sharded state through ``KishuSession``, whose restored
values must be bit-identical.  Whether they kept their shardings is
printed, not required.

Phase times are wall times of this one run on the chip, not a benchmark.
Everything runs in this one process (a chip belongs to one process).  The
last line of standard output is a JSON object naming the device; without
a TPU the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "smollm-360m"
CHUNK_BYTES = 1 << 16          # ManagedTrainingSession's own chunk size
TRAIN_STEPS = 3
RESET_LAYER = 7                # the layer whose moments the sparse cell zeroes
SHARDED_STEPS = 3
LOSS_RTOL = 1e-2               # sharded vs one-chip loss, per step
MAIN_KERNELS = ("chunk_hash", "delta_pack", "delta_codec", "patch_scatter")


def log(msg: str) -> None:
    print(msg, flush=True)


class Phases:
    """Wall time of each phase, on the host clock.  Each phase ends in a
    host transfer of its results, so the device work is inside it."""

    def __init__(self, devices: str):
        self.devices = devices

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        log(f"phase {name}: {time.perf_counter() - t0:.3f} s wall "
            f"(one run on {self.devices}; not a benchmark)")


class CompileWatch:
    """Compilations of this process: persistent-cache hits and misses and
    the seconds spent in backend compiles (JAX's monitoring events)."""

    def __init__(self):
        import jax.monitoring as mon

        self.hits = self.misses = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def report(self, cache_dir: str) -> None:
        log(f"compile cache {cache_dir}: {self.hits} hits, {self.misses} "
            f"misses, {self.compile_s:.1f} s in backend compiles")


def host_copy(ns) -> Dict[str, np.ndarray]:
    """Host copy of every leaf of a namespace (device arrays are
    immutable, so their host value stays as it was)."""
    return {name: np.asarray(ns[name]) for name in ns.names()}


def mismatches(ns, want: Dict[str, np.ndarray]) -> List[str]:
    """Names whose restored leaf is missing, extra, or not bit-identical to
    its host copy (dtype, shape and bytes)."""
    names = set(ns.names())
    bad = sorted(names ^ set(want))
    for name, ref in want.items():
        if name not in names:
            continue
        got = np.asarray(ns[name])
        if got.dtype != ref.dtype or got.shape != ref.shape \
                or got.tobytes() != ref.tobytes():
            bad.append(name)
    return bad


def kernel_calls(session) -> Dict[tuple, int]:
    """(kernel, backend) -> dispatches, from the session's registry."""
    return {(c["labels"]["kernel"], c["labels"]["backend"]): int(c["value"])
            for c in session.obs.registry.to_doc()["counters"]
            if c["name"] == "kishu_kernel_calls_total"}


def reset_layer_moments(ns, layer: int) -> None:
    """The sparse cell: reset the optimizer for one block — zero the AdamW
    moments of ``layer`` in every stacked per-layer tensor."""
    for name in ns.names():
        if name.startswith(("state/opt/mu/stages/", "state/opt/nu/stages/")):
            ns[name] = ns[name].at[layer].set(0)


def word_packing(seed: int) -> List[str]:
    """The 32-bit words the device packs from 16- and 8-bit arrays (every
    bit pattern drawn, odd lengths padded) against the host's bytes viewed
    as little-endian words; returns what differed."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.chunk_hash.ops import _to_words

    failed: List[str] = []
    rng = np.random.default_rng(seed)
    for dtype, bits, shape in ((jnp.bfloat16, np.uint16, (4096, 1536)),
                               (jnp.int8, np.uint8, (4097, 3))):
        host = rng.integers(0, np.iinfo(bits).max + 1, shape,
                            dtype=bits).view(dtype)
        got = np.asarray(jax.jit(_to_words)(jax.device_put(host)))
        raw = host.tobytes()
        want = np.frombuffer(raw + b"\0" * (-len(raw) % 4), "<u4")
        differ = int(np.count_nonzero(got != want))
        log(f"word packing {np.dtype(dtype).name}{list(shape)}: {differ} of "
            f"{want.size} words differ from the host bytes")
        if differ:
            failed.append(f"word packing of {np.dtype(dtype).name} differs")
    return failed


def main_path(cfg, phase: Callable, store_dir: str, seed: int, *,
              backend: str = "pallas", chunk_bytes: int = CHUNK_BYTES,
              layer: int = RESET_LAYER) -> List[str]:
    """The one-chip run; returns what failed (empty when all passed).
    ``backend`` is the one every kernel must have run on."""
    from repro.core.chunkstore import open_store
    from repro.optim.adamw import AdamWConfig
    from repro.train.loop import ManagedTrainingSession

    failed = word_packing(seed)
    sess = ManagedTrainingSession(cfg, AdamWConfig(), open_store(
        f"dir://{store_dir}"), chunk_bytes=chunk_bytes)
    kishu = sess.kishu
    kishu.register("reset_layer_moments", reset_layer_moments)
    writes = {}

    with phase("attach"):
        c_attach = sess.attach(seed=seed)
    writes["attach"] = kishu.last_run.write
    want_attach = host_copy(sess.ns)
    with phase("train"):
        c_train = sess.train(TRAIN_STEPS)
    writes["train"] = kishu.last_run.write
    loss = float(sess.ns["metrics/last_loss"])
    log(f"train: loss after {TRAIN_STEPS} steps {loss:.6f}")
    if not np.isfinite(loss):
        failed.append(f"train loss {loss} is not finite")
    want_train = host_copy(sess.ns)
    with phase("sparse"):
        c_sparse = kishu.run("reset_layer_moments", layer=layer,
                             _message=f"reset moments of layer {layer}")
    writes["sparse"] = kishu.last_run.write
    want_sparse = host_copy(sess.ns)
    zeroed = [n for n in sess.ns.names()
              if n.startswith("state/opt/mu/stages/")
              and not np.asarray(sess.ns[n])[layer].any()]
    if not zeroed:
        failed.append("the sparse cell zeroed no moments")

    checkouts = {}
    for name, target, want, after in (
            ("checkout_sparse", c_train, want_train, "train"),
            ("checkout_train", c_attach, want_attach, "attach"),
            ("checkout_codec", c_sparse, want_sparse, "sparse")):
        with phase(name):
            checkouts[name] = sess.checkout(target)
        bad = mismatches(sess.ns, want)
        log(f"{name}: {len(want) - len(bad)}/{len(want)} leaves "
            f"bit-identical to the host copy after {after}")
        failed += [f"{name}: {n} differs" for n in bad]

    for cell, w in writes.items():
        log(f"{cell}: WriteStats covs_packed={w.covs_packed} "
            f"chunks_encoded={w.chunks_encoded} "
            f"bytes_dev2host={w.bytes_dev2host} "
            f"bytes_written={w.bytes_written} "
            f"kernel_fallbacks={w.kernel_fallbacks}")
    for cell, st in checkouts.items():
        log(f"{cell}: CheckoutStats covs_scattered={st.covs_scattered} "
            f"covs_patched={st.covs_patched} covs_loaded={st.covs_loaded} "
            f"bytes_host2dev={st.bytes_host2dev} "
            f"kernel_fallbacks={st.kernel_fallbacks}")
    calls = kernel_calls(kishu)
    for (kernel, used), n in sorted(calls.items()):
        log(f"kernel {kernel}: backend {used} ({n} calls)")
    fallbacks = kishu.obs.kernel_fallbacks()
    log(f"kernel_fallbacks: {fallbacks}")

    if fallbacks:
        failed.append(f"kernel_fallbacks = {fallbacks}")
    for kernel in MAIN_KERNELS:
        used = {b for (k, b), n in calls.items() if k == kernel and n}
        if used != {backend}:
            failed.append(f"kernel {kernel} ran on {sorted(used) or 'none'},"
                          f" not {backend}")
    if not writes["sparse"].covs_packed:
        failed.append("covs_packed is 0 for the sparse cell")
    if not writes["sparse"].chunks_encoded:
        failed.append("chunks_encoded is 0 for the sparse cell")
    if not checkouts["checkout_sparse"].covs_scattered:
        failed.append("covs_scattered is 0 for checkout_sparse")
    sess.close()
    return failed


def sharded_path(cfg, phase: Callable, store_dir: str, seed: int, *,
                 n_devices: int = 4,
                 chunk_bytes: int = CHUNK_BYTES) -> List[str]:
    """The four-chip run; returns what failed (empty when all passed)."""
    import jax
    import jax.numpy as jnp

    from repro.core import KishuSession
    from repro.core.chunkstore import open_store
    from repro.data.pipeline import DataState, TokenPipeline
    from repro.launch.mesh import make_local_mesh
    from repro.optim.adamw import AdamWConfig
    from repro.sharding.rules import ShardingRules
    from repro.train import step as step_lib

    failed: List[str] = []
    if len(jax.devices()) != n_devices:
        return [f"{len(jax.devices())} devices, not {n_devices}"]
    opt = AdamWConfig()
    pipe = TokenPipeline(cfg.vocab_size, 8, 64)
    batches, ds = [], DataState(seed, 0)
    for _ in range(SHARDED_STEPS + 1):
        b, ds = pipe.next_batch(ds)
        batches.append(b)
    step = step_lib.make_train_step(cfg, opt, remat=False)

    def init_state():
        return step_lib.init_train_state(cfg, jax.random.key(seed), opt)

    ref_losses = []
    with phase("steps_1chip"):
        one = jax.jit(step)
        state = init_state()
        for b in batches[:SHARDED_STEPS]:
            state, m = one(state, {k: jnp.asarray(v) for k, v in b.items()})
            ref_losses.append(float(m["loss"]))
        del state

    mesh = make_local_mesh(model=2)
    rules = ShardingRules(cfg, mesh)
    abstract = step_lib.abstract_train_state(cfg, opt)
    pshard = rules.param_shardings(abstract["params"])
    sshard = {"params": pshard,
              "opt": {"mu": pshard, "nu": pshard,
                      "count": rules.replicated()},
              "step": rules.replicated(), "rng": rules.replicated()}
    bshard = rules.batch_spec(batches[0])
    sharded = jax.jit(step, in_shardings=(sshard, bshard),
                      out_shardings=(sshard, rules.replicated()))
    losses = []
    with phase(f"steps_{n_devices}chips"), mesh:
        state = jax.device_put(init_state(), sshard)
        for b in batches[:SHARDED_STEPS]:
            state, m = sharded(state, jax.device_put(b, bshard))
            losses.append(float(m["loss"]))
    for i, (a, r) in enumerate(zip(losses, ref_losses)):
        rel = abs(a - r) / max(abs(r), 1e-6)
        log(f"step {i}: loss {a:.6f} on {n_devices} chips, {r:.6f} on one "
            f"chip (relative difference {rel:.2e})")
        if not rel <= LOSS_RTOL:
            failed.append(f"step {i}: loss {a} vs {r} on one chip")

    holder = {"state": state}
    kishu = KishuSession(open_store(f"dir://{store_dir}"),
                         chunk_bytes=chunk_bytes)

    def put_state(ns):
        ns.set_tree("state", holder["state"])

    def train_more(ns):
        new, _ = sharded(ns.get_tree("state"),
                         jax.device_put(batches[-1], bshard))
        ns.set_tree("state", new)

    kishu.register("put_state", put_state)
    kishu.register("train_more", train_more)
    with phase("commit_sharded"):
        c_state = kishu.run("put_state")
    want = host_copy(kishu.ns)
    before = {n: kishu.ns[n].sharding for n in kishu.ns.names()}
    with mesh:
        kishu.run("train_more")
    with phase("checkout_sharded"):
        kishu.checkout(c_state)
    bad = mismatches(kishu.ns, want)
    log(f"checkout_sharded: {len(want) - len(bad)}/{len(want)} leaves "
        f"bit-identical to the host copy of the sharded state")
    failed += [f"checkout_sharded: {n} differs" for n in bad]
    kept = sum(1 for n, s in before.items()
               if getattr(kishu.ns[n], "sharding", None) == s)
    log(f"checkout_sharded: {kept}/{len(before)} restored leaves kept "
        f"their sharding")
    log(f"kernel_fallbacks: {kishu.obs.kernel_fallbacks()}")
    kishu.close()
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "sharded train step and its one-chip reference")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import use_compile_cache
    from repro.models.config import get_config

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: JAX found no devices: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    kind = devices[0].device_kind
    cache_dir = use_compile_cache()
    compiles = CompileWatch()
    log(f"device: {kind} x {len(devices)}; compile cache {cache_dir}")

    store_dir = tempfile.mkdtemp(prefix="kishu-chip-smoke-")
    free = shutil.disk_usage(store_dir).free
    log(f"store: {store_dir} ({free / 2**30:.1f} GiB free)")
    cfg = get_config(ARCH)
    phase = Phases(f"{len(devices)} x {kind}")
    try:
        if args.chips == 4:
            failed = sharded_path(cfg, phase, store_dir, args.seed)
        else:
            failed = main_path(cfg, phase, store_dir, args.seed)
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    compiles.report(cache_dir)
    for f in failed:
        print(f"FAILED: {f}", file=sys.stderr)
    print(json.dumps({"ok": not failed,
                      "device": {"platform": devices[0].platform,
                                 "kind": kind, "count": len(devices)}}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
