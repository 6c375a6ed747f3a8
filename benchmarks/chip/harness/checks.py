"""What decides ``correct``: each number compared, beside its limit."""
from __future__ import annotations

import dataclasses
import statistics

import jax
import jax.numpy as jnp


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit  # NaN fails


def _bits(x):
    if x.dtype.itemsize == 2:
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    if x.dtype.itemsize == 4:
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    if x.dtype.itemsize == 1:
        return jax.lax.bitcast_convert_type(x, jnp.uint8).astype(jnp.uint32)
    raise TypeError(f"no checksum for dtype {x.dtype}")


@jax.jit
def checksums(tree):
    """Two position-weighted sums of every leaf's bits, modulo 2**32. Each
    weight is odd, so a change of any one word changes both sums."""
    out = []
    for x in jax.tree.leaves(tree):
        b = _bits(x).ravel()
        i = jnp.arange(b.size, dtype=jnp.uint32)
        w1 = i * jnp.uint32(0x9E3779B1) | jnp.uint32(1)
        w2 = (i ^ jnp.uint32(0x5BD1E995)) * jnp.uint32(0x85EBCA6B) | jnp.uint32(1)
        out.append(jnp.stack([jnp.sum(b * w1), jnp.sum(b * w2)]))
    return jnp.stack(out)


def leaves_differ(a, b) -> int:
    """Leaves whose checksums differ between two ``checksums`` results."""
    import numpy as np
    a, b = np.asarray(a), np.asarray(b)
    if a.shape != b.shape:
        return max(a.shape[0], b.shape[0])
    return int(np.sum(np.any(a != b, axis=1)))


def loss_gap(prog: list, ref: list) -> float:
    """Widest relative gap between the program's and the reference's loss."""
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref, strict=True))


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf's gap between the two norms, over the larger of that
    leaf's reference norm and the median leaf's."""
    med = statistics.median(ref.values())
    keys = [k for k in ref if keep is None or k in keep]
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def moving_leaves(grad_norms: dict, share: float = 1e-3) -> set:
    """Leaves whose first gradient in the reference is at least ``share`` of
    the median leaf's; the others move under Adam by round-off alone."""
    med = statistics.median(grad_norms.values())
    return {k for k, v in grad_norms.items() if v >= share * med}


def training_checks(prog: dict, ref: dict, limits: dict) -> list:
    """The three numbers of a training cell, from ``train_readings``-shaped
    dicts of the program and of the reference."""
    keep = moving_leaves(ref["grad_norms"])
    return [
        Check("loss_gap", loss_gap(prog["losses"], ref["losses"]), limits["loss_gap"]),
        Check("grad_gap", norm_gap(prog["grad_norms"], ref["grad_norms"]),
              limits["grad_gap"]),
        Check("change_gap", norm_gap(prog["change_norms"], ref["change_norms"], keep),
              limits["change_gap"]),
    ]
