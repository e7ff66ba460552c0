"""What the chunk kernels share: the backend choice and the word-tile layout.

Backend: the Pallas kernels are TPU (Mosaic) kernels, so they run where the
array lives on a TPU; everywhere else the jnp reference runs, and it stays
the oracle the kernels are tested against.  The choice is made from the
platform, never by trying a kernel and catching its error: on a TPU a
kernel that fails raises.

Layout: Mosaic tiles the last two dimensions of a block by (8, 128) 32-bit
words.  A chunk of W uint32 words is therefore presented as an (R, 128)
tile, R = W / 128, rather than as a (1, W) row; a block of one chunk is
``(1, R, 128)``, which always equals the array's last two dimensions.
Chunks narrower than the kernel's minimum are zero-padded at their end.
"""
from __future__ import annotations

LANES = 128
SUBLANES = 8
TILE_WORDS = LANES * SUBLANES          # one (8, 128) vreg of 32-bit words


def platform_backend(x=None) -> str:
    """"pallas" when ``x`` (or, without an array, the default backend) is
    on a TPU, else "ref"."""
    import jax

    if isinstance(x, jax.Array):
        platform = next(iter(x.devices())).platform
    else:
        platform = jax.default_backend()
    return "pallas" if platform == "tpu" else "ref"


def tile_words(words, min_words: int = LANES):
    """uint32/int32 [n, W] -> [n, R, 128] with R * 128 = max(W, min_words)
    (W a power of two, ``min_words`` one >= 128); the padding words are
    zero."""
    import jax.numpy as jnp

    n, w = words.shape
    wp = max(w, min_words)
    if wp > w:
        words = jnp.pad(words, ((0, 0), (0, wp - w)))
    return words.reshape(n, wp // LANES, LANES)


def lane_pair(a, b):
    """(1, 128) vectors -> one (1, 128) vector holding a in lane 0 and b in
    lane 1 (a lane-dense store instead of two scalar stores)."""
    import jax
    import jax.numpy as jnp

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.where(lane == 0, a, jnp.where(lane == 1, b, 0))
