"""Plain reference of a Llama-architecture decoder, as published for
SmolLM-135M (HF ``LlamaForCausalLM``): token embedding, per layer RMSNorm,
grouped-query attention with rotary embeddings (half-split, as HF's
``rotate_half``) and a causal mask, RMSNorm, SwiGLU MLP, a final RMSNorm and
the output head tied to the embedding. Training is that of
``harness/reference.py`` over these logits, as the configuration file states
its optimizer. ``train_flops`` counts a training step's operations and
``TINY`` holds the sizes of the CPU rehearsals.

Straight ``jax.numpy`` in float32 with every product at ``HIGHEST``
precision: no kernel, cache or batching. It imports nothing of the program
and takes nothing the program made: the weights come from ``init`` and the
seed. To fit one chip it runs one sequence at a time and recomputes each
layer in the backward pass; neither changes the mathematics.

``mode="int8"`` is the control: every matrix product, forward and backward,
on operands rounded to int8 with one absmax scale per tensor.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops
from harness.reference import dot, rms, rope, train_readings as _train_readings

MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "mlp_norm")
# the configuration's sizes in the CPU rehearsals; the rest as published
TINY = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 256}


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


# --------------------------------------------------------------- forward


def hidden(p: dict, toks, m: dict, mode: str = "f32"):
    """Final-normed hidden states (S, d) of one sequence ``toks`` (S,)."""
    d, h, kv, hd, f, L, V = dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = toks.shape[0]
    x = p["embed"][toks]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def layer(x, lp):
        a = rms(x, lp["attn_norm"], eps)
        q = dot("sd,de->se", a, lp["wq"], mode).reshape(s, h, hd)
        k = dot("sd,de->se", a, lp["wk"], mode).reshape(s, kv, hd)
        v = dot("sd,de->se", a, lp["wv"], mode).reshape(s, kv, hd)
        q, k = rope(q, theta), rope(k, theta)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        sc = dot("qhd,khd->hqk", q, k, mode) / np.sqrt(hd)
        sc = jnp.where(causal[None], sc, -jnp.inf)
        o = dot("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, mode)
        x = x + dot("se,ed->sd", o.reshape(s, h * hd), lp["wo"], mode)
        b = rms(x, lp["mlp_norm"], eps)
        g = dot("sd,df->sf", b, lp["w_gate"], mode)
        u = dot("sd,df->sf", b, lp["w_up"], mode)
        x = x + dot("sf,fd->sd", jax.nn.silu(g) * u, lp["w_down"], mode)
        return x, None

    layers = {k: p[k] for k in MATRICES + NORMS}
    x, _ = jax.lax.scan(layer, x, layers)
    return rms(x, p["final_norm"], eps)


def logits(p: dict, toks, m: dict, mode: str = "f32"):
    """Logits (S, V) of one sequence; ``p`` in float32."""
    return dot("sd,vd->sv", hidden(p, toks, m, mode), p["embed"], mode)


def train_readings(p0: dict, batches: list, m: dict, opt: dict,
                   mode: str = "f32", grad_rows: int = 0) -> dict:
    """``harness.reference.train_readings`` over this architecture's logits."""
    return _train_readings(logits, p0, batches, m, opt, mode, grad_rows)


# ----------------------------------------------------------------- count


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix product per token: the
    attention and MLP projections of every layer and the output head
    (the tied embedding counts once, as the head)."""
    d, h, kv, hd, f, L, V = dims(m)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return L * per_layer + d * V


def train_flops(m: dict, batch: int, seq: int) -> float:
    """A training step's operations (recomputation not counted): 6 N D for
    the matrix products plus causal attention."""
    d, h, kv, hd, f, L, V = dims(m)
    return (flops.matmul_flops(matmul_params(m), batch * seq)
            + flops.causal_attention_flops(batch, seq, L, h, hd, hd))
