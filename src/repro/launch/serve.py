"""Batched serving driver: prefill + decode with KV/SSM caches.

    PYTHONPATH=src python -m repro.launch.serve --arch mamba2-780m --reduced \
        --batch 4 --prompt-len 32 --gen 16

Serving state (params + caches) lives in a Kishu session too: a "prefill"
command materializes caches as state, so a server can snapshot/branch
per-request-batch cache state (prefix reuse across branches) and roll back a
cancelled generation — the serving analogue of path exploration (§7.5.2).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.compile_cache import use_compile_cache
from repro.models.config import get_config
from repro.models.testing import reduced as reduce_cfg
from repro.models import lm
from repro.train import step as step_lib


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-780m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduce_cfg(cfg)

    key = jax.random.key(0)
    params = lm.init_params(cfg, key)
    decode = jax.jit(step_lib.make_decode_step(cfg))

    b, plen = args.batch, args.prompt_len
    total = plen + args.gen
    prompts = jax.random.randint(jax.random.key(1), (b, plen), 0,
                                 cfg.vocab_size)
    caches = lm.init_caches(cfg, b, total,
                            enc_seq=plen if cfg.enc_dec else 0)
    if cfg.enc_dec:
        enc = jax.random.normal(jax.random.key(2), (b, plen, cfg.d_model),
                                jnp.dtype(cfg.dtype))
        caches["enc_out"] = lm.encode(cfg, params,
                                      {"enc_embeds": enc}, remat=False)

    # prefill via decode loop (teacher-forcing the prompt)
    t0 = time.monotonic()
    tok = prompts[:, :1]
    out_tokens = [tok]
    for t in range(total - 1):
        batch = {"tokens": tok, "index": jnp.asarray(t, jnp.int32)}
        if cfg.frontend == "vision":
            batch = {"embeds": params["embed"][tok[:, 0]][:, None, :],
                     "index": jnp.asarray(t, jnp.int32)}
        nxt, caches = decode(params, caches, batch)
        tok = prompts[:, t + 1:t + 2] if t + 1 < plen else nxt
        out_tokens.append(tok)
    dt = time.monotonic() - t0
    gen = jnp.concatenate(out_tokens, axis=1)
    print(f"arch={cfg.name} batch={b} generated {args.gen} tokens/seq "
          f"in {dt:.2f}s ({b*total/dt:.1f} tok/s incl prefill)")
    print("sample:", np.asarray(gen[0, plen:plen + 12]))


if __name__ == "__main__":
    main()
