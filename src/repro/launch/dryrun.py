import os

from repro.configs.xla_flags import apply_xla_tuning, force_host_device_count
force_host_device_count(512)    # merged, not clobbered: user XLA_FLAGS win
apply_xla_tuning()              # opt-in ($KISHU_XLA_TUNING=1), no-op on CPU
# ^ MUST run before jax's first init: the backend locks XLA_FLAGS then.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds abstract inputs (ShapeDtypeStruct — zero allocation),
  2. derives in/out shardings from ShardingRules on the production mesh,
  3. ``jit(step).lower(...).compile()`` — proving the distribution config is
     coherent (sharding divisibility, collective legality, memory layout),
  4. records ``memory_analysis()`` / ``cost_analysis()`` and the collective
     byte volume parsed from the optimized HLO into a JSON artifact under
     ``--out`` (default ``artifacts/dryrun`` in the working directory).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--force]
"""
import argparse
import json
import re
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.launch.mesh import make_production_mesh
from repro.models.config import get_config
from repro.configs.shapes import SHAPES, input_specs, shape_applicable, cells
from repro.sharding.rules import ShardingRules
from repro.optim.adamw import AdamWConfig
from repro.train import step as step_lib

ART_DIR = os.path.join("artifacts", "dryrun")

DTYPE_BYTES = {
    "f64": 8, "u64": 8, "s64": 8, "c64": 8, "f32": 4, "u32": 4, "s32": 4,
    "bf16": 2, "f16": 2, "u16": 2, "s16": 2, "pred": 1, "u8": 1, "s8": 1,
    "f8e4m3fn": 1, "f8e5m2": 1, "c128": 16, "token": 0, "opaque": 0,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(text: str) -> int:
    """Sum byte sizes of every typed shape literal in an HLO result type."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, Any]:
    """Parse optimized HLO; sum result bytes per collective op kind."""
    out = {k: 0 for k in COLLECTIVES}
    counts = {k: 0 for k in COLLECTIVES}
    for line in hlo_text.splitlines():
        s = line.strip()
        eq = s.find(" = ")
        if eq < 0:
            continue
        rhs = s[eq + 3:]
        m = re.match(r"((?:\([^)]*\))|(?:[a-z0-9]+\[[0-9,]*\][^ ]*)) "
                     r"([a-z0-9-]+)", rhs)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        # match e.g. all-reduce, all-reduce-start, all-gather-done
        for kind in COLLECTIVES:
            if op == kind or op.startswith(kind + "-"):
                if op.endswith("-done"):
                    break                      # counted at -start
                out[kind] += _shape_bytes(result_type)
                counts[kind] += 1
                break
    out_counts = {f"n_{k}": v for k, v in counts.items()}
    return {**out, **out_counts,
            "total": sum(out[k] for k in COLLECTIVES)}


def _tree_device_bytes(tree, shardings, n_devices: int) -> int:
    """Analytic per-device bytes of a sharded abstract pytree."""
    leaves = jax.tree.leaves(tree)
    shard_leaves = jax.tree.leaves(shardings,
                                   is_leaf=lambda x: hasattr(x, "spec"))
    total = 0
    for leaf, sh in zip(leaves, shard_leaves):
        nbytes = np.prod(leaf.shape, dtype=np.int64) * np.dtype(leaf.dtype).itemsize
        try:
            ways = int(np.prod([1] + [
                0 or _axis_size(sh, ax) for ax in _spec_axes(sh)]))
        except Exception:  # noqa: BLE001
            ways = 1
        total += int(nbytes) // max(ways, 1)
    return total


def _spec_axes(sh):
    axes = []
    for entry in sh.spec:
        if entry is None:
            continue
        if isinstance(entry, tuple):
            axes.extend(entry)
        else:
            axes.append(entry)
    return axes


def _axis_size(sh, ax):
    return dict(zip(sh.mesh.axis_names, sh.mesh.devices.shape))[ax]


def stage_unit_counts(cfg) -> list:
    """Current number of units per stage (decoder stages [+ encoder])."""
    from repro.models import lm as lm_lib
    counts = [s.n_units for s in lm_lib.build_stages(cfg)]
    if cfg.enc_dec:
        counts.append(lm_lib.encoder_stages(cfg)[0].n_units)
    return counts


def with_stage_counts(cfg, counts: list):
    """Config surgery: rebuild cfg so each stage has the given unit count."""
    from repro.models import lm as lm_lib
    stages = lm_lib.build_stages(cfg)
    kw = {}
    if cfg.moe is not None and cfg.moe.n_dense_layers:
        assert len(stages) == 2
        import dataclasses
        kw["moe"] = dataclasses.replace(cfg.moe, n_dense_layers=counts[0])
        kw["n_layers"] = counts[0] + counts[1] * len(stages[1].unit)
    else:
        assert len(stages) == 1
        kw["n_layers"] = counts[0] * len(stages[0].unit)
    if cfg.enc_dec:
        kw["n_encoder_layers"] = counts[-1]
    return cfg.replace(**kw)


def calibration_points(cfg) -> list:
    """(variant_cfg, counts) points for solving cost = outer + sum N_i*body_i:
    a base with 1 unit per stage plus one +1 point per stage."""
    n_stages = len(stage_unit_counts(cfg))
    base = [1] * n_stages
    pts = [list(base)]
    for i in range(n_stages):
        v = list(base)
        v[i] = 2
        pts.append(v)
    return [(with_stage_counts(cfg, c), c) for c in pts]


def build_cell(arch: str, shape: str, mesh, *, unroll: bool = False,
               cfg_override=None,
               rules_opts: Optional[dict] = None) -> Dict[str, Any]:
    """Build (fn, args, in/out shardings) for one cell."""
    cfg = cfg_override if cfg_override is not None else get_config(arch)
    spec = input_specs(cfg, shape)
    rules = ShardingRules(cfg, mesh, **(rules_opts or {}))
    hidden_sharding = (rules.hidden_spec(SHAPES[shape].global_batch,
                                         SHAPES[shape].seq_len)
                       if rules.seq_shard_activations else None)
    opt_cfg = AdamWConfig(
        moment_dtype="bfloat16" if cfg.param_counts()["total"] > 5e10
        else "float32")

    if spec["kind"] == "train":
        state = step_lib.abstract_train_state(cfg, opt_cfg)
        pshard = rules.param_shardings(state["params"])
        state_shard = {
            "params": pshard,
            "opt": {"mu": pshard, "nu": pshard,
                    "count": rules.replicated()},
            "step": rules.replicated(),
            "rng": rules.replicated(),
        }
        batch_shard = rules.batch_spec(spec["batch"])
        fn = step_lib.make_train_step(cfg, opt_cfg, remat=True,
                                      unroll=unroll,
                                      hidden_sharding=hidden_sharding)
        jfn = jax.jit(fn, in_shardings=(state_shard, batch_shard),
                      out_shardings=(state_shard, rules.replicated()),
                      donate_argnums=(0,))
        return {"jfn": jfn, "args": (state, spec["batch"]),
                "cfg": cfg, "rules": rules,
                "arg_shards": (state_shard, batch_shard)}

    from repro.models import lm
    params = lm.abstract_params(cfg)
    pshard = rules.param_shardings(params)
    if spec["kind"] == "prefill":
        batch_shard = rules.batch_spec(spec["batch"])
        fn = step_lib.make_prefill_step(cfg, unroll=unroll,
                                        hidden_sharding=hidden_sharding)
        jfn = jax.jit(fn, in_shardings=(pshard, batch_shard),
                      out_shardings=rules.logits_spec(
                          SHAPES[shape].global_batch))
        return {"jfn": jfn, "args": (params, spec["batch"]),
                "cfg": cfg, "rules": rules,
                "arg_shards": (pshard, batch_shard)}

    # decode
    bsz = SHAPES[shape].global_batch
    caches = spec["caches"]
    cshard = rules.cache_spec(caches, bsz)
    batch_shard = rules.batch_spec(spec["batch"])
    fn = step_lib.make_decode_step(cfg, unroll=unroll)
    jfn = jax.jit(fn, in_shardings=(pshard, cshard, batch_shard),
                  out_shardings=(rules.batch_spec(
                      jax.ShapeDtypeStruct((bsz, 1), np.int32)), cshard),
                  donate_argnums=(1,))
    return {"jfn": jfn, "args": (params, caches, spec["batch"]),
            "cfg": cfg, "rules": rules,
            "arg_shards": (pshard, cshard, batch_shard)}


def run_cell(arch: str, shape: str, mesh_kind: str, *,
             out_dir: str = ART_DIR, force: bool = False,
             save: bool = True, variant: str = "",
             rules_opts: Optional[dict] = None) -> Dict[str, Any]:
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{variant}" if variant else ""
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)

    cfg = get_config(arch)
    ok, why = shape_applicable(cfg, shape)
    rec: Dict[str, Any] = {"arch": arch, "shape": shape, "mesh": mesh_kind,
                           "variant": variant, "rules_opts": rules_opts or {}}
    if not ok:
        rec.update({"status": "skip", "reason": why})
        if save:
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    n_dev = int(np.prod(mesh.devices.shape))
    t0 = time.monotonic()
    try:
        from repro.sharding import context as shctx
        with mesh:
            cell = build_cell(arch, shape, mesh, rules_opts=rules_opts)
            with shctx.moe_weight_gather(cell["rules"]):
                lowered = cell["jfn"].lower(*cell["args"])
            t_lower = time.monotonic() - t0
            compiled = lowered.compile()
            t_compile = time.monotonic() - t0 - t_lower

            cost = {}
            try:
                ca = compiled.cost_analysis()
                if isinstance(ca, (list, tuple)):
                    ca = ca[0]
                cost = {k: float(v) for k, v in (ca or {}).items()
                        if isinstance(v, (int, float)) and (
                            k in ("flops", "transcendentals")
                            or k.startswith("bytes accessed"))}
            except Exception as e:  # noqa: BLE001
                cost = {"error": str(e)}

            memory = {}
            try:
                ma = compiled.memory_analysis()
                for f in ("argument_size_in_bytes", "output_size_in_bytes",
                          "temp_size_in_bytes", "alias_size_in_bytes",
                          "generated_code_size_in_bytes"):
                    if hasattr(ma, f):
                        memory[f] = int(getattr(ma, f))
            except Exception as e:  # noqa: BLE001
                memory = {"error": str(e)}

            hlo = compiled.as_text()
            coll = collective_bytes(hlo)

            arg_dev_bytes = sum(
                _tree_device_bytes(a, s, n_dev)
                for a, s in zip(cell["args"], cell["arg_shards"]))

            pc = cfg.param_counts()
            rec.update({
                "status": "ok",
                "n_devices": n_dev,
                "lower_s": round(t_lower, 2),
                "compile_s": round(t_compile, 2),
                "cost_analysis": cost,
                "memory_analysis": memory,
                "collectives": coll,
                "arg_bytes_per_device": int(arg_dev_bytes),
                "params_total": pc["total"],
                "params_active": pc["active"],
                "hlo_lines": hlo.count("\n"),
            })
    except Exception as e:  # noqa: BLE001
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-4000:]})
    if save:
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def _cost_vector(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    vec = {"flops": float((ca or {}).get("flops", 0.0)),
           "bytes": float((ca or {}).get("bytes accessed", 0.0))}
    for k in COLLECTIVES:
        vec[f"coll_{k}"] = float(coll[k])
    vec["coll_total"] = float(coll["total"])
    return vec


def calibrate_cell(arch: str, shape: str, mesh_kind: str, *,
                   out_dir: str = ART_DIR, force: bool = False,
                   variant: str = "",
                   rules_opts: Optional[dict] = None) -> Optional[dict]:
    """Scan-aware cost calibration (XLA cost analysis counts a while body
    once).  Compiles small *unrolled* variants — 1 unit per stage plus one
    (+1 unit) point per stage — and solves

        cost = outer + sum_i N_i * body_i

    exactly for the linear per-stage costs, then evaluates at the real unit
    counts.  Stored under "calibrated" in the cell artifact."""
    suffix = f"__{variant}" if variant else ""
    path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rec = json.load(f)
    if rec.get("status") != "ok":
        return None
    if "calibrated" in rec and not force:
        return rec["calibrated"]
    rules_opts = rules_opts or rec.get("rules_opts") or {}

    cfg = get_config(arch)
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    points = calibration_points(cfg)
    vecs = []
    try:
        from repro.sharding import context as shctx
        with mesh:
            for vcfg, counts in points:
                cell = build_cell(arch, shape, mesh, unroll=True,
                                  cfg_override=vcfg, rules_opts=rules_opts)
                with shctx.moe_weight_gather(cell["rules"]):
                    compiled = cell["jfn"].lower(*cell["args"]).compile()
                vecs.append((counts, _cost_vector(compiled)))
    except Exception as e:  # noqa: BLE001
        rec["calibrated"] = {"error": f"{type(e).__name__}: {e}"}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        return rec["calibrated"]

    base_counts, base = vecs[0]
    n_true = stage_unit_counts(cfg)
    calibrated = {"points": [{"counts": c, **v} for c, v in vecs],
                  "n_units": n_true}
    for metric in base:
        bodies = [vecs[1 + i][1][metric] - base[metric]
                  for i in range(len(n_true))]
        outer = base[metric] - sum(bodies)
        calibrated[metric] = outer + sum(
            n * b for n, b in zip(n_true, bodies))
        calibrated[f"{metric}_outer"] = outer
        calibrated[f"{metric}_bodies"] = bodies
    rec["calibrated"] = calibrated
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    return calibrated


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--calibrate", action="store_true",
                    help="add scan-aware calibrated costs to artifacts")
    ap.add_argument("--out", default=ART_DIR)
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    todo = []
    if args.all:
        for arch, shape, _ok, _why in cells():
            for mk in meshes:
                todo.append((arch, shape, mk))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape, mk) for mk in meshes]

    failures = 0
    for arch, shape, mk in todo:
        t0 = time.monotonic()
        if args.calibrate:
            cal = calibrate_cell(arch, shape, mk, out_dir=args.out,
                                 force=args.force)
            dt = time.monotonic() - t0
            if cal is None:
                print(f"[n/a  ] {arch:24s} {shape:12s} {mk:6s}", flush=True)
            elif "error" in cal:
                failures += 1
                print(f"[error] {arch:24s} {shape:12s} {mk:6s} ({dt:5.1f}s) "
                      f"{cal['error'][:120]}", flush=True)
            else:
                print(f"[ok   ] {arch:24s} {shape:12s} {mk:6s} ({dt:5.1f}s) "
                      f"cal_flops={cal['flops']:.3e} "
                      f"cal_coll={cal['coll_total']:.3e}B", flush=True)
            continue
        rec = run_cell(arch, shape, mk, out_dir=args.out, force=args.force)
        dt = time.monotonic() - t0
        status = rec["status"]
        extra = ""
        if status == "ok":
            fl = rec["cost_analysis"].get("flops", 0)
            extra = (f" flops={fl:.3e} coll={rec['collectives']['total']:.3e}B"
                     f" arg/dev={rec['arg_bytes_per_device']/2**30:.2f}GiB"
                     f" compile={rec['compile_s']:.0f}s")
        elif status == "error":
            failures += 1
            extra = " " + rec["error"][:160]
        print(f"[{status:5s}] {arch:24s} {shape:12s} {mk:6s}"
              f" ({dt:5.1f}s){extra}", flush=True)
    if failures:
        print(f"{failures} FAILURES", flush=True)
        sys.exit(1)
    print("dry-run complete", flush=True)


if __name__ == "__main__":
    main()
