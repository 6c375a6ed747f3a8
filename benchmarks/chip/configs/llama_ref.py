"""Plain reference of a Llama-architecture decoder, as published for
SmolLM-135M (HF ``LlamaForCausalLM``): token embedding, per layer RMSNorm,
grouped-query attention with rotary embeddings (half-split, as HF's
``rotate_half``) and a causal mask, RMSNorm, SwiGLU MLP, a final RMSNorm and
the output head tied to the embedding. Training is mean token
cross-entropy, clipping by global norm and AdamW with decoupled weight
decay, as the configuration file states them.

Straight ``jax.numpy`` in float32 with every product at ``HIGHEST``
precision: no kernel, cache or batching. It imports nothing of the program
and takes nothing the program made: the weights come from ``init`` and the
seed. To fit one chip it runs one sequence at a time and recomputes each
layer in the backward pass; neither changes the mathematics.

``mode="int8"`` is the control: every matrix product, forward and backward,
on operands rounded to int8 with one absmax scale per tensor.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "mlp_norm")


# --------------------------------------------------------------- weights


def dims(m: dict) -> tuple:
    d, h, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    hd = m.get("head_dim") or d // h
    return d, h, kv, hd, m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"]


def shapes(m: dict) -> dict:
    d, h, kv, hd, f, L, V = dims(m)
    return {
        "embed": (V, d), "final_norm": (d,),
        "attn_norm": (L, d), "wq": (L, d, h * hd), "wk": (L, d, kv * hd),
        "wv": (L, d, kv * hd), "wo": (L, h * hd, d), "mlp_norm": (L, d),
        "w_gate": (L, d, f), "w_up": (L, d, f), "w_down": (L, f, d),
    }


def init(key, m: dict, param_dtype=jnp.bfloat16) -> dict:
    """Weights from ``key``: matrices N(0, initializer_range) stored in
    ``param_dtype``, norm weights 1 in float32."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out[name] = (w * m["initializer_range"]).astype(param_dtype)
    return out


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


def _dot(spec: str, a, b, mode: str):
    if mode == "int8":
        return _int8_einsum(spec)(a, b)
    return jnp.einsum(spec, a, b, precision=HI)


# --------------------------------------------------------------- forward


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (S, H, D); rotary embedding at positions 0..S-1."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(0, half, dtype=jnp.float32) * 2 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv  # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def hidden(p: dict, toks, m: dict, mode: str = "f32"):
    """Final-normed hidden states (S, d) of one sequence ``toks`` (S,)."""
    d, h, kv, hd, f, L, V = dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = toks.shape[0]
    x = p["embed"][toks]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def layer(x, lp):
        a = _rms(x, lp["attn_norm"], eps)
        q = _dot("sd,de->se", a, lp["wq"], mode).reshape(s, h, hd)
        k = _dot("sd,de->se", a, lp["wk"], mode).reshape(s, kv, hd)
        v = _dot("sd,de->se", a, lp["wv"], mode).reshape(s, kv, hd)
        q, k = _rope(q, theta), _rope(k, theta)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        sc = _dot("qhd,khd->hqk", q, k, mode) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = _dot("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, mode)
        x = x + _dot("se,ed->sd", o.reshape(s, h * hd), lp["wo"], mode)
        b = _rms(x, lp["mlp_norm"], eps)
        g = _dot("sd,df->sf", b, lp["w_gate"], mode)
        u = _dot("sd,df->sf", b, lp["w_up"], mode)
        x = x + _dot("sf,fd->sd", jax.nn.silu(g) * u, lp["w_down"], mode)
        return x, None

    layers = {k: p[k] for k in MATRICES + NORMS}
    x, _ = jax.lax.scan(layer, x, layers)
    return _rms(x, p["final_norm"], eps)


def logits(p: dict, toks, m: dict, mode: str = "f32"):
    """Logits (S, V) of one sequence; ``p`` in float32."""
    return _dot("sd,vd->sv", hidden(p, toks, m, mode), p["embed"], mode)


def _ce_sum(p, inputs, labels, m, mode):
    lg = logits(p, inputs, m, mode)
    lz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lz - gold)


@functools.partial(jax.jit, static_argnames=("mode", "mkey"))
def _seq_value_grad(p, inputs, labels, *, mode, mkey):
    return jax.value_and_grad(_ce_sum)(p, inputs, labels, dict(mkey), mode)


def loss_and_grads(p32: dict, batch: dict, m: dict, mode: str = "f32",
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
                               mode=mode, mkey=mkey)
        total = total + l
        if i < g_rows:
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
    return total / n, jax.tree.map(lambda g: g * (rows / g_rows) / n, grads)


# -------------------------------------------------------------- training


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


def train_readings(p0: dict, batches: list, m: dict, opt: dict,
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
        loss, g = loss_and_grads(p32, batch, m, mode, grad_rows)
        del p32
        losses.append(float(loss))
        p, gc, mstate, vstate = _adamw_step(p, g, mstate, vstate, t, opt)
        if g1 is None:
            g1 = leaf_norms(gc)
        del g, gc
    change = leaf_norms({k: p[k].astype(jnp.float32) - p0[k].astype(jnp.float32)
                         for k in p})
    return {"losses": losses, "grad_norms": g1, "change_norms": change}
