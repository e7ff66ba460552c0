"""The benchmark's cells at their chip-share sizes, compiled for a described
TPU v5e chip: the AdamW training step of each configuration, the edit cell,
and the state fingerprint.  Nothing runs; the TPU's compiler refuses here
what it would refuse on the chip (a shape, or more memory than the chip
holds)."""
import json
import os
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _cells(name):
    from chipbench.cells import Cells

    cfg = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                     .read_text())
    return Cells(cfg)


def _abstract(tree, sharding):
    import jax

    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


@pytest.mark.parametrize("name", ["qwen3_1p7b", "mamba2_780m"])
def test_train_step_compiles_for_v5e(one_chip, name):
    import jax
    import jax.numpy as jnp

    cells = _cells(name)
    params, mu, nu, count = jax.eval_shape(cells._init, jax.random.key(0))
    args = _abstract((params, mu, nu, count), one_chip)
    toks = jax.ShapeDtypeStruct((8, 65), jnp.int32, sharding=one_chip)
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    compiled = cells._train.lower(*args, toks, lr).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM_BYTES


def test_edit_cell_compiles_for_v5e(one_chip):
    import sys

    import jax
    import jax.numpy as jnp

    from chipbench.traffic import load_op

    edit = sys.modules[load_op("edit_rows").__module__].edit
    cells = _cells("mamba2_780m")
    params, mu, nu, _ = jax.eval_shape(cells._init, jax.random.key(0))
    table = _abstract((params["embed"], mu["embed"], nu["embed"]), one_chip)
    row0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    key = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one_chip)
    edit.lower(*table, row0, key, 32).compile()


def test_fingerprint_compiles_for_v5e(one_chip):
    import jax

    from chipbench import digest

    cells = _cells("qwen3_1p7b")
    params, mu, nu, count = jax.eval_shape(cells._init, jax.random.key(0))
    leaves = jax.tree.leaves(_abstract((params, mu, nu, count), one_chip))
    digest._sums.lower(leaves).compile()
