"""The training job the train and resume drivers share: the program's
compiled step with its state, made from the seed, and the readings that the
reference is compared with.

Set-up builds one step object and one state, drives them through the first
``check_steps`` steps on batches that all differ, reads the program's
numbers on the way, and hands the same object on to the window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from harness import data
from repro.configs.base import ShapeConfig
from repro.models.transformer import build_model
from repro.runtime.train import (TrainState, jit_train_step,
                                 make_dp_xdfs_train_step, state_shardings)

import program  # noqa: E402  (drivers/ is on the path of a driver)


def _norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).ravel())
                      for x in jax.tree.leaves(tree)])


class Trainer:
    def __init__(self, run):
        self.run = run
        cell = run.cell
        self.conf, self.traffic = cell.config, cell.traffic
        self.m = self.conf["model"]
        self.ref, self.layout = cell.reference(), cell.layout()
        self.B, self.S = self.traffic["batch"], self.traffic["seq"]
        self.cfg = program.model_config(self.conf, self.layout)
        self.mesh = program.mesh(cell.chips)
        ring = self.traffic.get("sync", "xla") == "ring"
        self.model = build_model(self.cfg, self.mesh, "train", plain=ring)
        self.opt = program.optimizer(self.conf)
        self.ss = state_shardings(self.model, self.opt)
        max_norm = self.conf["deployment"]["optimizer"]["max_grad_norm"]
        shape = ShapeConfig("custom", self.S, self.B, "train")
        with self.mesh:
            if ring:
                self.step_fn = make_dp_xdfs_train_step(
                    self.model, self.opt, max_grad_norm=max_norm)
            else:
                self.step_fn = jit_train_step(self.model, self.opt, shape,
                                              max_grad_norm=max_norm)
        in_sh = jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                             self.model.input_specs(shape),
                             is_leaf=lambda s: isinstance(s, P))
        self._batch = jax.jit(functools.partial(
            data.train_batch, batch=self.B, seq=self.S, vocab=self.m["vocab_size"]),
            out_shardings=in_sh)
        self.param_dtype = jnp.dtype(self.conf["deployment"]["param_dtype"])
        program.check_layout(self.layout, self.model, jax.eval_shape(
            lambda: self.ref.init(jax.random.key(0), self.m, self.param_dtype)))

        def make(key):
            params = program.to_program(self.layout,
                                        self.ref.init(key, self.m, self.param_dtype))
            return TrainState(params, self.opt.init(params), jnp.zeros((), jnp.int32))
        self._make = jax.jit(make, out_shardings=self.ss)
        self.tokens_per_step = self.B * self.S
        self.reseed(run.seed)

    def reseed(self, seed: int):
        """A new state from ``seed``: weights and batches both follow it."""
        self.state = None
        self.key = data.seed_key(seed, "train")
        self.wkey = data.seed_key(seed, "weights")
        with self.mesh:
            self.state = self._make(self.wkey)
        self.steps_done = 0

    def batch(self, i: int):
        return self._batch(self.key, jnp.int32(i))

    def step(self, i: int) -> float:
        """Step on batch ``i``; returns its loss, on the host."""
        with self.mesh:
            self.state, metrics = self.step_fn(self.state, self.batch(i))
        return float(metrics["loss"])

    def first_steps(self) -> dict:
        """Steps 1..n from the seed: the program's numbers for the checks."""
        n = self.traffic["check_steps"]
        norms = jax.jit(lambda t: _norms(program.from_program(self.layout, t)))
        names = sorted(program.from_program(self.layout, self.state.params))
        p0 = jax.tree.map(jnp.copy, self.state.params)
        losses, grads = [], None
        for i in range(n):
            losses.append(self.step(i))
            if i == 0:
                # the optimizer's first moment after one step is (1 - b1) g
                g = np.asarray(norms(self.state.opt_state.m)) / (1 - self.opt.b1)
                grads = dict(zip(names, map(float, g)))
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                             self.state.params, p0)
        change = dict(zip(names, map(float, np.asarray(norms(delta)))))
        del p0, delta
        self.steps_done = n
        return {"losses": losses, "grad_norms": grads, "change_norms": change}

    def state_like(self):
        return jax.eval_shape(lambda: self.state)

    def state_bytes(self) -> int:
        return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(self.state))

    def drop(self):
        self.state = None

    def reference_readings(self, mode: str = "f32", fault: str = "") -> dict:
        """The reference over the same first steps, from the same seed.
        Run it after the program's state is dropped. ``fault`` plants one in
        the reference put in the program's place: "half_batch" (the mean
        over the first half of each batch) or "no_exchange" (the gradient of
        the first chip's rows alone, on a cell of several chips)."""
        n = self.traffic["check_steps"]
        p0 = jax.jit(lambda k: self.ref.init(k, self.m, self.param_dtype))(self.wkey)
        batch = jax.jit(functools.partial(
            data.train_batch, batch=self.B, seq=self.S, vocab=self.m["vocab_size"]))
        batches = [batch(self.key, jnp.int32(i)) for i in range(n)]
        grad_rows = 0
        if fault == "half_batch":
            batches = [jax.tree.map(lambda x: x[: self.B // 2], b) for b in batches]
        elif fault == "no_exchange":
            grad_rows = self.B // self.run.cell.chips
        return self.ref.train_readings(p0, batches, self.m,
                                       self.conf["deployment"]["optimizer"], mode,
                                       grad_rows)

