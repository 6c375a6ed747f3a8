"""Operations a training step requires, from its shapes (recomputation not
counted): the terms that each reference's ``train_flops`` adds up for its
architecture."""
from __future__ import annotations


def matmul_flops(params: int, tokens: int) -> float:
    """Forward and backward of the matrix products of ``params`` weights
    that each of ``tokens`` tokens passes through: 6 N D."""
    return 6 * params * tokens


def causal_attention_flops(batch: int, seq: int, layers: int, heads: int,
                           qk_dim: int, v_dim: int) -> float:
    """Forward and backward of causal attention: per head and sequence,
    QK^T takes ``seq**2 / 2 * qk_dim`` multiply-adds forward and PV
    ``seq**2 / 2 * v_dim``; backward twice the forward."""
    fwd = 2 * (seq * seq / 2) * (qk_dim + v_dim) * heads
    return 3 * fwd * batch * layers
