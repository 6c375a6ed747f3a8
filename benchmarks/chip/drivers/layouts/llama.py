"""A Llama-architecture configuration (``configs/llama_ref.py``) as the
program holds it: where each of the reference's leaves lies in the program's
parameter tree, and which ``ModelConfig`` fields the configuration's
``model`` keys set. ``drivers/program.py`` applies both."""

# one block kind ("g"), scanned over the layers
_BLOCK = ("blocks", "b0_g")
PATHS = {
    "embed": ("embed",), "final_norm": ("final_norm",),
    "attn_norm": _BLOCK + ("norm1",), "wq": _BLOCK + ("wq",),
    "wk": _BLOCK + ("wk",), "wv": _BLOCK + ("wv",),
    "wo": _BLOCK + ("wo",), "mlp_norm": _BLOCK + ("norm2",),
    "w_gate": _BLOCK + ("mlp", "w_gate"),
    "w_up": _BLOCK + ("mlp", "w_in"),
    "w_down": _BLOCK + ("mlp", "w_out"),
}


def fields(m: dict) -> dict:
    """``ModelConfig`` fields from a Hugging Face Llama ``config.json``."""
    return dict(
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"], num_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        act=m["hidden_act"], tie_embeddings=m["tie_word_embeddings"])
