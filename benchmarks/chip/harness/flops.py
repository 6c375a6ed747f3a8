"""Operations a step requires, from its shapes (recomputation not counted)."""
from __future__ import annotations


def matmul_params(m: dict) -> int:
    """Parameters that take part in a matrix product per token: the
    attention and MLP projections of every layer and the output head
    (the tied embedding counts once, as the head)."""
    d, f, L = m["hidden_size"], m["intermediate_size"], m["num_hidden_layers"]
    hd = m.get("head_dim") or d // m["num_attention_heads"]
    q = m["num_attention_heads"] * hd
    kv = m["num_key_value_heads"] * hd
    per_layer = d * q + 2 * d * kv + q * d + 3 * d * f
    return L * per_layer + d * m["vocab_size"]


def causal_attention_flops(m: dict, batch: int, seq: int) -> float:
    """Forward and backward of causal attention: QK^T and PV each take
    ``seq**2 / 2 * head_dim`` multiply-adds per head and sequence forward,
    twice that backward."""
    hd = m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]
    fwd = 2 * 2 * (seq * seq / 2) * hd * m["num_attention_heads"]
    return 3 * fwd * batch * m["num_hidden_layers"]


def train_step_flops(m: dict, batch: int, seq: int) -> float:
    """6 N D for the matrix products plus causal attention."""
    return 6 * matmul_params(m) * batch * seq + causal_attention_flops(m, batch, seq)
