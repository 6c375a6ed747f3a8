"""The program under test, as the drivers reach it: its model configuration
built from a configuration file, its parameter tree filled with the
benchmark's own weights, and that tree read back in the reference's layout.

What differs between architectures comes from the configuration's layout
(``drivers/layouts/<layout>.py``, ``Cell.layout()``): ``PATHS``, each
reference leaf's path in the program's tree, and ``fields(model)``, the
``ModelConfig`` fields that the configuration's ``model`` keys set.
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


def model_config(conf: dict, layout):
    """The program's ``ModelConfig`` with every size the file states."""
    base = get_config(conf["program_arch"])
    return dataclasses.replace(base, **layout.fields(conf["model"]),
                               fsdp=conf["deployment"].get("fsdp", base.fsdp))


def optimizer(conf: dict) -> AdamW:
    o = conf["deployment"]["optimizer"]
    return AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                 weight_decay=o["weight_decay"], state_dtype=o["state_dtype"])


def mesh(chips: int):
    return make_local_mesh(chips, 1)


def to_program(layout, ref: dict) -> dict:
    out: dict = {}
    for name, path in layout.PATHS.items():
        node = out
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = ref[name]
    return out


def from_program(layout, tree: dict) -> dict:
    out = {}
    for name, path in layout.PATHS.items():
        node = tree
        for k in path:
            node = node[k]
        out[name] = node
    return out


def check_layout(layout, model, ref_like: dict) -> None:
    """The program's parameters have exactly the reference's shapes and
    stored dtypes, leaf for leaf."""
    want = jax.tree.map(lambda x: (tuple(x.shape), jnp.dtype(x.dtype)),
                        to_program(layout, ref_like))
    have = jax.tree.map(lambda x: (tuple(x.shape), jnp.dtype(x.dtype)),
                        model.abstract())
    if want != have:
        raise ValueError(f"parameter layout differs: program {have} vs benchmark {want}")


def param_shardings(model):
    specs = model.policy.param_specs(model.defs)
    return jax.tree.map(lambda s: NamedSharding(model.policy.mesh, s), specs,
                        is_leaf=lambda s: isinstance(s, P))
