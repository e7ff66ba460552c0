"""Distribution tests that need multiple devices — run in subprocesses with
their own XLA_FLAGS (the main test process must keep 1 device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

pytestmark = pytest.mark.slow    # JAX jit-heavy; fast lane: -m "not slow"


SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_sharded_train_step_runs_on_8_devices():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import get_config
        from repro.models.testing import reduced
        from repro.optim.adamw import AdamWConfig
        from repro.train import step as step_lib
        from repro.launch.mesh import make_local_mesh
        from repro.sharding.rules import ShardingRules

        mesh = make_local_mesh(model=4)
        cfg = reduced(get_config("qwen3-1.7b"), n_layers=2).replace(
            d_model=64, n_heads=4, n_kv_heads=4, head_dim=16)
        oc = AdamWConfig(lr=1e-3)
        rules = ShardingRules(cfg, mesh)
        state = step_lib.init_train_state(cfg, jax.random.key(0), oc)
        pshard = rules.param_shardings(state["params"])
        sshard = {"params": pshard,
                  "opt": {"mu": pshard, "nu": pshard, "count": rules.replicated()},
                  "step": rules.replicated(), "rng": rules.replicated()}
        state = jax.device_put(state, sshard)
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 32), jnp.int32)}
        batch = jax.device_put(batch, rules.batch_spec(batch))
        fn = jax.jit(step_lib.make_train_step(cfg, oc, remat=True),
                     in_shardings=(sshard, rules.batch_spec(batch)),
                     out_shardings=(sshard, rules.replicated()))
        with mesh:
            state2, metrics = fn(state, batch)
        assert np.isfinite(float(metrics["loss"]))
        print("SHARDED_OK", float(metrics["loss"]))
    """)
    assert "SHARDED_OK" in out


def test_sharded_equals_single_device():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.models import get_config
        from repro.models.testing import reduced
        from repro.optim.adamw import AdamWConfig
        from repro.train import step as step_lib
        from repro.launch.mesh import make_local_mesh
        from repro.sharding.rules import ShardingRules

        cfg = reduced(get_config("smollm-360m"), n_layers=2)
        oc = AdamWConfig(lr=1e-3)
        state = step_lib.init_train_state(cfg, jax.random.key(0), oc)
        batch = {"tokens": jax.random.randint(jax.random.key(1), (4, 16), 0, 100),
                 "labels": jax.random.randint(jax.random.key(2), (4, 16), 0, 100)}
        fn = step_lib.make_train_step(cfg, oc, remat=False)
        # single-device reference
        s_ref, m_ref = fn(jax.device_put(state), batch)
        # sharded
        mesh = make_local_mesh(model=4)
        rules = ShardingRules(cfg, mesh)
        pshard = rules.param_shardings(state["params"])
        sshard = {"params": pshard,
                  "opt": {"mu": pshard, "nu": pshard, "count": rules.replicated()},
                  "step": rules.replicated(), "rng": rules.replicated()}
        with mesh:
            s_sh, m_sh = jax.jit(fn, in_shardings=(sshard, rules.batch_spec(batch)),
                                 out_shardings=(sshard, rules.replicated()))(
                jax.device_put(state, sshard), jax.device_put(batch, rules.batch_spec(batch)))
        assert abs(float(m_ref["loss"]) - float(m_sh["loss"])) < 1e-3, \
            (float(m_ref["loss"]), float(m_sh["loss"]))
        l_ref = jax.tree.leaves(s_ref["params"])[0]
        l_sh = jax.tree.leaves(s_sh["params"])[0]
        np.testing.assert_allclose(np.asarray(l_ref), np.asarray(l_sh),
                                   atol=2e-2, rtol=2e-2)
        print("EQUIV_OK")
    """)
    assert "EQUIV_OK" in out


def test_take_rows_states_gather_sharding_on_explicit_mesh():
    """On an explicitly sharded mesh the embedding table and the token ids
    are both split over `data`; the gather's output is sharded like the
    ids instead of raising DuplicateSpecError."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.models.lm import take_rows

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Explicit,) * 2)
        table = np.arange(64 * 32, dtype=np.float32).reshape(64, 32)
        ids = np.random.default_rng(0).integers(0, 64, (4, 16))
        t = jax.device_put(table, NamedSharding(mesh, P("model", "data")))
        i = jax.device_put(ids.astype(np.int32),
                           NamedSharding(mesh, P("data", None)))
        with mesh:
            got = jax.jit(take_rows)(t, i)
        assert got.sharding.spec == P("data", None, None), got.sharding
        np.testing.assert_array_equal(np.asarray(got), table[ids])
        print("TAKE_ROWS_OK")
    """)
    assert "TAKE_ROWS_OK" in out


def test_compressed_psum_numerics():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compressed_psum, residual_init

        mesh = jax.make_mesh((8,), ("data",))
        # per-device distinct gradients, replicated layout
        def make(i):
            return {"w": jnp.full((64,), float(i + 1)),
                    "b": jnp.linspace(-1, 1, 32) * (i + 1)}
        grads = make(0)
        res = residual_init(grads)

        # emulate 8 different device grads by running shard_map over stacked
        # data: use vmap-free approach — call compressed_psum on a pytree of
        # [8, ...] arrays sharded over data, inside shard_map semantics.
        stacked = {"w": jnp.stack([make(i)["w"] for i in range(8)]),
                   "b": jnp.stack([make(i)["b"] for i in range(8)])}
        def body(g):
            g = jax.tree.map(lambda x: x[0], g)    # local shard [1,...] -> [...]
            r = jax.tree.map(lambda x: jnp.zeros_like(x), g)
            def inner(gl, rl):
                gl32 = gl.astype(jnp.float32) + rl
                amax = jax.lax.pmax(jnp.max(jnp.abs(gl32)), "data")
                scale = jnp.maximum(amax, 1e-12) / 127.0
                q = jnp.clip(jnp.round(gl32 / scale), -127, 127).astype(jnp.int8)
                s = jax.lax.psum(q.astype(jnp.int32), "data")
                return (s.astype(jnp.float32) * scale / 8.0)[None]
            return jax.tree.map(inner, g, r)
        sharded = jax.device_put(
            stacked, jax.tree.map(lambda _: jax.NamedSharding(mesh, P("data")), stacked))
        with mesh:
            out = jax.shard_map(body, mesh=mesh, in_specs=(P("data"),),
                                out_specs=P("data"), check_vma=False)(sharded)
        got = jax.tree.map(lambda x: np.asarray(x)[0], out)
        want = {k: np.mean([np.asarray(make(i)[k]) for i in range(8)], axis=0)
                for k in ("w", "b")}
        for k in ("w", "b"):
            scale = np.abs(want[k]).max() + 1e-9
            err = np.abs(got[k] - want[k]).max() / scale
            assert err < 0.02, (k, err)
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


def test_dryrun_machinery_small_mesh():
    """The dry-run builder works end-to-end on a small mesh with a reduced
    arch (fast proxy for the 512-device run, which runs separately)."""
    out = run_sub("""
        import jax, numpy as np
        from repro.launch import dryrun
        from repro.models.config import get_config, register
        from repro.models.testing import reduced

        base = get_config("qwen3-1.7b")
        small = reduced(base, n_layers=2).replace(name="tiny-test")
        register(small)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        cell = dryrun.build_cell("tiny-test", "train_4k", mesh)
        lowered = cell["jfn"].lower(*cell["args"])
        compiled = lowered.compile()
        hlo = compiled.as_text()
        coll = dryrun.collective_bytes(hlo)
        assert coll["total"] > 0, "expected collectives in sharded train step"
        print("DRYRUN_SMALL_OK", coll["total"])
    """)
    assert "DRYRUN_SMALL_OK" in out


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes, _shape_bytes
    hlo = """
  %ar = bf16[128,256]{1,0} all-reduce(%x), replica_groups={}
  %ag.1 = f32[512]{0} all-gather(%y), dimensions={0}
  %rs = (f32[64]{0}, f32[64]{0}) reduce-scatter(%a, %b), dimensions={0}
  %cp = u8[100]{0} collective-permute-start(%z)
  %cpd = u8[100]{0} collective-permute-done(%cp)
  %other = f32[2,2]{1,0} add(%p, %q)
"""
    c = collective_bytes(hlo)
    assert c["all-reduce"] == 128 * 256 * 2
    assert c["all-gather"] == 512 * 4
    assert c["reduce-scatter"] == 2 * 64 * 4
    assert c["collective-permute"] == 100       # start counted, done skipped
    assert c["n_all-reduce"] == 1
