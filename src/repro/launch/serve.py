"""Serving driver: batched prefill + decode loop with the sequence-sharded
(flash-decoding) KV cache layout.

Example (CPU, reduced config):
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
      --batch 4 --prompt-len 32 --gen 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core import spans
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.transformer import build_model


def generate(cfg, mesh, params, prompts, gen_tokens: int, greedy: bool = True,
             key=None):
    """prompts: (B, S) int32 (or (B,S,d) embeds for stub-frontend archs)."""
    with mesh:
        mp = build_model(cfg, mesh, "prefill")
        md = build_model(cfg, mesh, "decode")
        prefill = jax.jit(mp.prefill)
        decode = jax.jit(md.decode_step)

        # the first call builds the program (traced, lowered, compiled or
        # loaded from the compile cache) before it runs
        with spans.span("serve.prefill"):
            logits, caches = prefill(params, {"inputs": prompts})
        s = prompts.shape[1]
        out = []
        key = key if key is not None else jax.random.key(0)
        with spans.span("serve.decode"):
            for t in range(gen_tokens):
                if greedy:
                    nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
                else:
                    key, sub = jax.random.split(key)
                    nxt = jax.random.categorical(sub, logits[:, -1]).astype(jnp.int32)
                out.append(nxt)
                step_in = nxt[:, None]
                if cfg.frontend:  # stub frontend: embed via a fixed projection
                    step_in = jnp.zeros(
                        (prompts.shape[0], 1, cfg.d_model), jnp.bfloat16
                    )
                logits, caches = decode(
                    params, {"inputs": step_in, "caches": caches, "pos": jnp.int32(s + t)}
                )
        return jnp.stack(out, axis=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    mesh = make_local_mesh(1, 1)
    with mesh:
        model = build_model(cfg, mesh, "prefill")
        params = model.init(jax.random.key(0))
    if cfg.frontend:
        prompts = jax.random.normal(
            jax.random.key(1), (args.batch, args.prompt_len, cfg.d_model), jnp.bfloat16
        )
    else:
        prompts = jax.random.randint(
            jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
        )
    t0 = time.perf_counter()
    toks = generate(cfg, mesh, params, prompts, args.gen)
    dt = time.perf_counter() - t0
    print(f"[serve] generated {toks.shape} in {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s)")
    print(toks[0])


if __name__ == "__main__":
    main()
