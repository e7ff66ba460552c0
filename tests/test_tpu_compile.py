"""The main path's Pallas kernels compile for a TPU v5e.

Each kernel is lowered and compiled for one chip of a described (not
attached) ``v5e:2x2`` topology, at the widths of a training session
(64 KiB chunks in 4 MiB segments) and of ``KishuSession``'s 1 MiB default.
Interpret-mode tests prove the kernels' results; these prove that Mosaic
accepts their block shapes, stores and VMEM use — which interpret mode
cannot — without a chip.  Nothing runs: only shapes are passed.
"""
import os
import re

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")

SEG_BYTES = 4 << 20
CHUNKS = [1 << 16, 1 << 20]          # training session, KishuSession default


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, kernel, **static):
    """Compile ``fn`` for the described chip; the HLO must hold the Pallas
    kernel (a Mosaic custom call), not an XLA stand-in, as an instruction
    named ``kernel``: the profiler trace names its operations so, and the
    benchmark's roofline readers find them by that name."""
    compiled = fn.lower(*args, **static).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(rf"%{kernel}(\.\d+)? = .*tpu_custom_call", text)
    return compiled


@pytest.mark.parametrize("chunk_bytes", CHUNKS)
def test_chunk_hash_compiles(one_chip, chunk_bytes):
    import jax.numpy as jnp

    from repro.kernels.chunk_hash.ops import chunk_hash

    # a bf16 MLP weight of smollm-360m stacked over its 32 layers
    x = _spec((32, 960, 2560), jnp.bfloat16, one_chip)
    _compile(chunk_hash, x, chunk_bytes=chunk_bytes, backend="pallas",
             kernel="chunk_hash_pallas")


@pytest.mark.parametrize("chunk_bytes", CHUNKS)
def test_delta_pack_compiles(one_chip, chunk_bytes):
    import jax.numpy as jnp

    from repro.kernels.common import LANES
    from repro.kernels.delta_pack.kernel import delta_pack_pallas

    n = SEG_BYTES // chunk_bytes
    rows = chunk_bytes // 4 // LANES
    compiled = _compile(
        delta_pack_pallas,
        _spec((n, rows, LANES), jnp.uint32, one_chip),
        _spec((n, 2), jnp.uint32, one_chip),
        _spec((n,), jnp.int32, one_chip), kernel="delta_pack_pallas")
    # the compacted buffer lives in HBM: the program's output holds it
    assert compiled.memory_analysis().output_size_in_bytes >= SEG_BYTES


@pytest.mark.parametrize("chunk_bytes", CHUNKS)
def test_codec_encode_compiles(one_chip, chunk_bytes):
    import jax.numpy as jnp

    from repro.kernels.delta_codec.kernel import codec_encode_pallas
    from repro.kernels.delta_codec.ops import group_words_for

    w = chunk_bytes // 4
    rows = _spec((SEG_BYTES // chunk_bytes, w), jnp.uint32, one_chip)
    _compile(codec_encode_pallas, rows, gw=group_words_for(w),
             kernel="codec_encode_pallas")


@pytest.mark.parametrize("chunk_bytes", CHUNKS)
def test_patch_scatter_compiles(one_chip, chunk_bytes):
    import jax.numpy as jnp

    from repro.kernels.patch_scatter.kernel import patch_scatter_pallas

    w = chunk_bytes // 4
    n_chunks = (32 * 960 * 2560 * 4) // chunk_bytes      # an f32 moment
    k = 256 if chunk_bytes < (1 << 20) else 16
    _compile(patch_scatter_pallas,
             _spec((n_chunks, w), jnp.uint32, one_chip),
             _spec((k,), jnp.int32, one_chip),
             _spec((k, w), jnp.uint32, one_chip),
             kernel="patch_scatter_pallas")


@pytest.mark.parametrize("chunk_bytes", CHUNKS)
def test_block_diff_compiles(one_chip, chunk_bytes):
    import jax.numpy as jnp

    from repro.kernels.block_diff.ops import block_diff

    x = _spec((960, 2560), jnp.float32, one_chip)
    _compile(block_diff, x, x, chunk_bytes=chunk_bytes, backend="pallas",
             kernel="block_diff_pallas")
