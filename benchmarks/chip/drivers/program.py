"""The program under test, as the drivers reach it: its model configuration
built from a configuration file, its parameter tree filled with the
benchmark's own weights, and that tree read back in the reference's layout.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config
from repro.launch.mesh import make_local_mesh
from repro.optim import AdamW

# reference name -> path in the program's tree (one block kind, scanned)
_BLOCK = "b0_g"
_PATHS = {
    "embed": ("embed",), "final_norm": ("final_norm",),
    "attn_norm": ("blocks", _BLOCK, "norm1"), "wq": ("blocks", _BLOCK, "wq"),
    "wk": ("blocks", _BLOCK, "wk"), "wv": ("blocks", _BLOCK, "wv"),
    "wo": ("blocks", _BLOCK, "wo"), "mlp_norm": ("blocks", _BLOCK, "norm2"),
    "w_gate": ("blocks", _BLOCK, "mlp", "w_gate"),
    "w_up": ("blocks", _BLOCK, "mlp", "w_in"),
    "w_down": ("blocks", _BLOCK, "mlp", "w_out"),
}


def model_config(conf: dict):
    """The program's ``ModelConfig`` with every size the file states."""
    m, dep = conf["model"], conf["deployment"]
    base = get_config(conf["program_arch"])
    return dataclasses.replace(
        base,
        num_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        num_heads=m["num_attention_heads"], num_kv_heads=m["num_key_value_heads"],
        head_dim=m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"],
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        act=m["hidden_act"], tie_embeddings=m["tie_word_embeddings"],
        fsdp=dep.get("fsdp", base.fsdp))


def optimizer(conf: dict) -> AdamW:
    o = conf["deployment"]["optimizer"]
    return AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], state_dtype=o["state_dtype"])


def mesh(chips: int):
    return make_local_mesh(chips, 1)


def to_program(ref: dict) -> dict:
    out: dict = {}
    for name, path in _PATHS.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = ref[name]
    return out


def from_program(tree: dict) -> dict:
    out = {}
    for name, path in _PATHS.items():
        node = tree
        for k in path:
            node = node[k]
        out[name] = node
    return out


def check_layout(model, ref_like: dict) -> None:
    """The program's parameters have exactly the reference's shapes and
    stored dtypes, leaf for leaf."""
    want = jax.tree.map(lambda x: (tuple(x.shape), jnp.dtype(x.dtype)),
                        to_program(ref_like))
    have = jax.tree.map(lambda x: (tuple(x.shape), jnp.dtype(x.dtype)),
                        model.abstract())
    if want != have:
        raise ValueError(f"parameter layout differs: program {have} vs benchmark {want}")


def param_shardings(model):
    specs = model.policy.param_specs(model.defs)
    return jax.tree.map(lambda s: NamedSharding(model.policy.mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
