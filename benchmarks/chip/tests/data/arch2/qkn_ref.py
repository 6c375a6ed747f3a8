"""Plain reference of a second decoder architecture, for the test that adds
one as new files only: per layer RMSNorm, grouped-query attention with
per-head RMSNorm on queries and keys before the rotary embedding (as Qwen3
and OLMo 2 have them) and a causal mask, RMSNorm, a plain (not gated) GELU
MLP; a final RMSNorm and an output head apart from the embedding. Training
is that of ``harness/reference.py`` over these logits.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import flops
from harness.reference import dot, rms, rope, train_readings as _train_readings

LAYERS = ("attn_norm", "q_norm", "k_norm", "wq", "wk", "wv", "wo",
          "mlp_norm", "w_up", "w_down")
TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "vocab_size": 256}


def dims(m: dict) -> tuple:
    return (m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"],
            m["head_dim"], m["intermediate_size"], m["num_hidden_layers"], m["vocab_size"])


def shapes(m: dict) -> dict:
    d, h, kv, hd, f, L, V = dims(m)
    return {
        "embed": (V, d), "head": (d, V), "final_norm": (d,),
        "attn_norm": (L, d), "q_norm": (L, hd), "k_norm": (L, hd),
        "wq": (L, d, h * hd), "wk": (L, d, kv * hd), "wv": (L, d, kv * hd),
        "wo": (L, h * hd, d), "mlp_norm": (L, d), "w_up": (L, d, f), "w_down": (L, f, d),
    }


def init(key, m: dict, param_dtype=jnp.bfloat16) -> dict:
    """Matrices N(0, initializer_range) in ``param_dtype``, norm weights 1
    in float32."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes(m).items())):
        if name.endswith("norm"):
            out[name] = jnp.ones(shape, jnp.float32)
        else:
            w = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
            out[name] = (w * m["initializer_range"]).astype(param_dtype)
    return out


def logits(p: dict, toks, m: dict, mode: str = "f32"):
    """Logits (S, V) of one sequence; ``p`` in float32."""
    d, h, kv, hd, f, L, V = dims(m)
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    s = toks.shape[0]
    causal = jnp.tril(jnp.ones((s, s), bool))

    @jax.checkpoint
    def layer(x, lp):
        a = rms(x, lp["attn_norm"], eps)
        q = rms(dot("sd,de->se", a, lp["wq"], mode).reshape(s, h, hd), lp["q_norm"], eps)
        k = rms(dot("sd,de->se", a, lp["wk"], mode).reshape(s, kv, hd), lp["k_norm"], eps)
        v = dot("sd,de->se", a, lp["wv"], mode).reshape(s, kv, hd)
        q, k = rope(q, theta), rope(k, theta)
        k, v = jnp.repeat(k, h // kv, axis=1), jnp.repeat(v, h // kv, axis=1)
        sc = jnp.where(causal[None], dot("qhd,khd->hqk", q, k, mode) / np.sqrt(hd), -jnp.inf)
        o = dot("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, mode)
        x = x + dot("se,ed->sd", o.reshape(s, h * hd), lp["wo"], mode)
        u = dot("sd,df->sf", rms(x, lp["mlp_norm"], eps), lp["w_up"], mode)
        return x + dot("sf,fd->sd", jax.nn.gelu(u, approximate=True), lp["w_down"], mode), None

    x, _ = jax.lax.scan(layer, p["embed"][toks], {k: p[k] for k in LAYERS})
    return dot("sd,dv->sv", rms(x, p["final_norm"], eps), p["head"], mode)


def train_readings(p0: dict, batches: list, m: dict, opt: dict,
                   mode: str = "f32", grad_rows: int = 0) -> dict:
    return _train_readings(logits, p0, batches, m, opt, mode, grad_rows)


def train_flops(m: dict, batch: int, seq: int) -> float:
    """6 N D over the attention projections, the two MLP matrices and the
    head, plus causal attention."""
    d, h, kv, hd, f, L, V = dims(m)
    per_layer = 2 * d * h * hd + 2 * d * kv * hd + 2 * d * f
    return (flops.matmul_flops(L * per_layer + d * V, batch * seq)
            + flops.causal_attention_flops(batch, seq, L, h, hd, hd))
