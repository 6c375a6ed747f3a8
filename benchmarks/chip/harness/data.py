"""Token batches from the seed, made on the device.

The arithmetic is that of the program's seeded stream (a Zipf-flavoured
draw, ``vocab * u ** 2.2``), kept here so the yardstick cannot move with
the program. Every seed gives the same shapes; only the token values
differ. ``stream`` names a use (training batches, serving prompts), so two
uses of one seed draw different tokens.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STREAMS = {"train": 1, "prompts": 2, "weights": 3}


def seed_key(seed: int, stream: str) -> jax.Array:
    """A key from a seed of any size up to 64 bits."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, STREAMS[stream])


def tokens(key: jax.Array, shape, vocab: int) -> jax.Array:
    u = jax.random.uniform(key, shape, jnp.float32)
    t = (vocab * u ** 2.2).astype(jnp.int32)
    return jnp.minimum(t, vocab - 1)


def train_batch(key, step, *, batch: int, seq: int, vocab: int) -> dict:
    """The batch of training step ``step`` (0-based): inputs and labels."""
    t = tokens(jax.random.fold_in(key, step), (batch, seq + 1), vocab)
    return {"inputs": t[:, :-1], "labels": t[:, 1:]}


def prompts(key, n: int, length: int, vocab: int) -> jax.Array:
    return tokens(key, (n, length), vocab)
