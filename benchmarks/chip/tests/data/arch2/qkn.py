"""The second architecture of ``qkn_ref.py`` as the program holds it: the
query and key norms and the plain MLP inside the scanned block, the head
apart from the embedding."""

_BLOCK = ("blocks", "b0_g")
PATHS = {
    "embed": ("embed",), "head": ("head",), "final_norm": ("final_norm",),
    "attn_norm": _BLOCK + ("norm1",), "q_norm": _BLOCK + ("q_norm",),
    "k_norm": _BLOCK + ("k_norm",), "wq": _BLOCK + ("wq",), "wk": _BLOCK + ("wk",),
    "wv": _BLOCK + ("wv",), "wo": _BLOCK + ("wo",), "mlp_norm": _BLOCK + ("norm2",),
    "w_up": _BLOCK + ("mlp", "w_in"), "w_down": _BLOCK + ("mlp", "w_out"),
}


def fields(m: dict) -> dict:
    return dict(
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"], num_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"], act="gelu_plain",
        qk_norm=True, tie_embeddings=m["tie_word_embeddings"])
