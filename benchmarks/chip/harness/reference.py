"""What every architecture's plain reference (``configs/<reference>.py``)
builds from: products at ``HIGHEST`` precision or, for the control, on
operands rounded to int8; RMSNorm and rotary embeddings; and training, mean
token cross-entropy with clipping by global norm and AdamW with decoupled
weight decay, over a reference's ``logits(p, toks, m, mode)``.

Straight ``jax.numpy`` in float32, importing nothing of the program. To fit
one chip, training runs one sequence at a time; that does not change the
mathematics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------- products


def _fake_int8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


@functools.lru_cache(maxsize=None)
def _int8_einsum(spec: str):
    def plain(a, b):
        return jnp.einsum(spec, a, b, precision=HI)

    @jax.custom_vjp
    def f(a, b):
        return plain(_fake_int8(a), _fake_int8(b))

    def fwd(a, b):
        qa, qb = _fake_int8(a), _fake_int8(b)
        return plain(qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(plain, *res)
        return vjp(_fake_int8(g))

    f.defvjp(fwd, bwd)
    return f


def dot(spec: str, a, b, mode: str):
    """``einsum(spec, a, b)``; with ``mode="int8"`` (the control) every
    product, forward and backward, on operands rounded to int8 with one
    absmax scale per tensor."""
    if mode == "int8":
        return _int8_einsum(spec)(a, b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (S, H, D); rotary embedding at positions 0..S-1, half-split (as
    HF's ``rotate_half``)."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) * 2 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# -------------------------------------------------------------- training


def _ce_sum(p, inputs, labels, m, mode, logits):
    lg = logits(p, inputs, m, mode)
    lz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lz - gold)


@functools.partial(jax.jit, static_argnames=("mode", "mkey", "logits"))
def _seq_value_grad(p, inputs, labels, *, mode, mkey, logits):
    return jax.value_and_grad(_ce_sum)(p, inputs, labels, dict(mkey), mode, logits)


def loss_and_grads(logits, p32: dict, batch: dict, m: dict, mode: str = "f32",
                   grad_rows: int = 0):
    """Mean token cross-entropy of ``batch`` and its gradient, one
    sequence at a time. ``grad_rows`` > 0 takes the gradient of the first
    ``grad_rows`` sequences alone (a fault: a worker whose gradient was
    never exchanged)."""
    mkey = tuple(sorted((k, v) for k, v in m.items() if not isinstance(v, (list, dict))))
    rows, n = batch["labels"].shape[0], batch["labels"].size
    g_rows = grad_rows or rows
    total, grads = 0.0, None
    for i in range(rows):
        l, g = _seq_value_grad(p32, batch["inputs"][i], batch["labels"][i],
                               mode=mode, mkey=mkey, logits=logits)
        total = total + l
        if i < g_rows:
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / n, jax.tree.map(lambda g: g * (rows / g_rows) / n, grads)


def _adamw_step(p, g, mstate, vstate, t, opt):
    """One AdamW step after clipping ``g`` by its global norm. Parameters
    keep their stored dtype: the update is rounded to it, then added."""
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, opt["max_grad_norm"] / jnp.maximum(norm, 1e-12))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2 = opt["b1"], opt["b2"]
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    mstate = jax.tree.map(lambda mm, x: b1 * mm + (1 - b1) * x, mstate, g)
    vstate = jax.tree.map(lambda vv, x: b2 * vv + (1 - b2) * x * x, vstate, g)

    def upd(w, mm, vv):
        delta = (mm / c1) / (jnp.sqrt(vv / c2) + opt["eps"]) \
            + opt["weight_decay"] * w.astype(jnp.float32)
        u = (-opt["lr"] * delta).astype(w.dtype)
        return (w.astype(jnp.float32) + u.astype(jnp.float32)).astype(w.dtype)

    return jax.tree.map(upd, p, mstate, vstate), g, mstate, vstate


def leaf_norms(tree) -> dict:
    return {k: float(jnp.linalg.norm(jnp.asarray(v, jnp.float32).ravel()))
            for k, v in tree.items()}


def train_readings(logits, p0: dict, batches: list, m: dict, opt: dict,
                   mode: str = "f32", grad_rows: int = 0) -> dict:
    """What the checks compare, for ``len(batches)`` steps from ``p0``
    (stored dtypes): each step's loss, each leaf's norm of the first
    clipped gradient, and each leaf's norm of the change of the parameters
    over all the steps."""
    p = dict(p0)
    mstate = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), p)
    vstate = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32), p)
    losses, g1 = [], None
    for t, batch in enumerate(batches, start=1):
        p32 = jax.tree.map(lambda w: w.astype(jnp.float32), p)
        loss, g = loss_and_grads(logits, p32, batch, m, mode, grad_rows)
        del p32
        losses.append(float(loss))
        p, gc, mstate, vstate = _adamw_step(p, g, mstate, vstate, t, opt)
        if g1 is None:
            g1 = leaf_norms(gc)
        del g, gc
    change = leaf_norms({k: p[k].astype(jnp.float32) - p0[k].astype(jnp.float32)
                         for k in p})
    return {"losses": losses, "grad_norms": g1, "change_norms": change}
