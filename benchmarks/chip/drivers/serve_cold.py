"""Cold starts of a serving replica from the replicated store, back to back.

Set-up commits the serving params (made from the seed) to an in-process
store of one MetaNode and ``datanodes`` DataNodes at ``replication``, and
runs one cold start to warm up. Each cold start drops the previous params,
restores them from the store (``xdfs_ckpt.restore(cluster=...)``) and
answers one request of ``requests`` prompts of ``prompt`` tokens with
``gen`` greedy tokens through ``launch.serve.generate``, which builds its
programs anew on every call (they come from the compile cache). The window
starts cold starts while ``--seconds`` remain and runs each to its end. End
to end: ``ready_s``, the mean over cold starts from the start of the
restore to the answer on the host.

Checks: every restore bit for bit the committed params; after the window a
scrub of every DataNode finds each replica intact and no block is short of
its replicas; and the served tokens of ``sample_requests`` requests drawn
from the seed against the reference: ``token_gap_mean``, the mean over those
tokens of the gap by which a served token's logit lies below the
reference's best at its position. The widest such gap is recorded as a
fact, ``token_gap_max``: it reads only the nearest tie that rounding
flipped, and does not tell the int8 control from the program by three
times.
"""
from __future__ import annotations

import functools
import random
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import checks, data
from harness.checks import Check
from repro.checkpoint import xdfs_ckpt
from repro.cluster import ClusterClient, DataNode, MetaNode
from repro.launch.serve import generate
from repro.models.transformer import build_model

import program

NAME = "serve/params"


class Replica:
    def __init__(self, run, seed):
        cell = run.cell
        self.run, self.conf, self.traffic = run, cell.config, cell.traffic
        self.m = self.conf["model"]
        self.ref, self.layout = cell.reference(), cell.layout()
        self.cfg = program.model_config(self.conf, self.layout)
        self.mesh = program.mesh(cell.chips)
        model = build_model(self.cfg, self.mesh, "prefill")
        self.dtype = jnp.dtype(self.conf["deployment"]["param_dtype"])
        program.check_layout(self.layout, model, jax.eval_shape(
            lambda: self.ref.init(jax.random.key(0), self.m, self.dtype)))
        self.sh = program.param_shardings(model)
        t = self.traffic
        self._prompts = jax.jit(lambda k, i: data.prompts(
            jax.random.fold_in(k, i), t["requests"], t["prompt"], self.m["vocab_size"]))
        self._make = jax.jit(
            lambda k: program.to_program(self.layout, self.ref.init(k, self.m, self.dtype)),
            out_shardings=self.sh)
        self.reseed(seed)

    def reseed(self, seed):
        self.wkey = data.seed_key(seed, "weights")
        self.pkey = data.seed_key(seed, "prompts")

    def params(self):
        return self._make(self.wkey)

    def prompts(self, i):
        return self._prompts(self.pkey, jnp.int32(i))

    def answer(self, params, i) -> np.ndarray:
        return np.asarray(generate(self.cfg, self.mesh, params, self.prompts(i),
                                   self.traffic["gen"]))

    def token_gaps(self, answers: dict, mode: str = "f32") -> np.ndarray:
        """The gap, at every served token of ``answers`` (request index ->
        tokens), between the reference's best logit and the served token's.
        With ``mode="int8"`` the served tokens are the int8 reference's own
        choices at the same positions (the control)."""
        p32 = jax.tree.map(lambda w: w.astype(jnp.float32),
                           jax.jit(lambda k: self.ref.init(k, self.m, self.dtype))(self.wkey))
        lg = jax.jit(functools.partial(self.ref.logits, m=self.m))
        lg8 = jax.jit(functools.partial(self.ref.logits, m=self.m, mode="int8"))
        L, gaps = self.traffic["prompt"], []
        for i, toks in answers.items():
            seqs = jnp.concatenate([self.prompts(i), jnp.asarray(toks)], axis=1)
            for row in range(seqs.shape[0]):
                x = seqs[row, :-1]
                ref = np.asarray(lg(p32, x))[L - 1:]
                served = np.asarray(toks[row]) if mode == "f32" else \
                    np.asarray(lg8(p32, x))[L - 1:].argmax(-1)
                gaps.append(ref.max(-1) - ref[np.arange(len(served)), served])
        return np.concatenate(gaps)


def gap_readings(gaps: np.ndarray) -> dict:
    return {"token_gap_mean": float(gaps.mean()), "token_gap_max": float(gaps.max()),
            "tokens_not_best": int((gaps > 0).sum()), "tokens": int(gaps.size)}


class Store:
    """One MetaNode and its DataNodes, in this process, over loopback."""

    def __init__(self, workdir, datanodes, replication):
        self.meta = MetaNode(replication=replication).start()
        self.nodes = [DataNode(self.meta.address, f"{workdir}/node{i}",
                               node_id=f"node{i}").start() for i in range(datanodes)]
        self.cli = ClusterClient(self.meta.address)

    def replica_faults(self) -> int:
        bad = 0
        for n in self.nodes:
            rep = n.scrub_once()
            bad += len(rep.corrupt) + len(rep.missing)
        st = self.cli.state()
        return bad + st["under_replicated"] + len(st["lost"])

    def close(self):
        self.cli.close()
        for n in self.nodes:
            n.stop()
        self.meta.stop()


def run(run):
    t = run.cell.traffic
    rep = Replica(run, run.seed)
    store = Store(run.workdir, t["datanodes"], t["replication"])
    try:
        _serve(run, rep, store)
    finally:
        store.close()


def _serve(run, rep, store):
    t = rep.traffic
    params = rep.params()
    want = checks.checksums(params)
    n_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    xdfs_ckpt.save(params, NAME, step=0, cluster=store.cli)
    like = jax.eval_shape(lambda: params)
    del params

    def cold_start(i):
        t0 = time.perf_counter()
        with run.span("restore"):
            params, _ = xdfs_ckpt.restore(NAME, like, shardings=rep.sh, cluster=store.cli)
            jax.block_until_ready(params)
        with run.span("generate"):
            toks = rep.answer(params, i)
        return time.perf_counter() - t0, checks.checksums(params), toks

    _, warm_sums, _ = cold_start(0)
    times, sums, answers = [], [warm_sums], {}
    with run.window():
        t0 = time.perf_counter()
        i = 1
        while time.perf_counter() - t0 < run.seconds:
            dt, s, toks = cold_start(i)
            times.append(dt)
            sums.append(s)
            answers[i] = toks
            i += 1
    run.read_memory()
    run.attempted = len(times)
    run.e2e["ready_s"] = statistics.mean(times)
    run.facts.update(restored_bytes=n_bytes, cold_starts=len(times))
    restores_differ = sum(checks.leaves_differ(s, want) > 0 for s in sums)
    faults = store.replica_faults()
    shape_bad = sum(a.shape != (t["requests"], t["gen"])
                    or not ((a >= 0) & (a < rep.m["vocab_size"])).all()
                    for a in answers.values())
    picked = sorted(random.Random(run.seed).sample(
        sorted(answers), min(t["sample_requests"], len(answers))))
    gaps = gap_readings(rep.token_gaps({i: answers[i] for i in picked})) if picked \
        else {"token_gap_mean": float("inf")}
    run.facts.update(gaps)
    run.checks = [Check("restores_differ", float(restores_differ), 0.0),
                  Check("replica_faults", float(faults), 0.0),
                  Check("answers_malformed", float(shape_bad), 0.0),
                  Check("token_gap_mean", gaps["token_gap_mean"],
                        rep.conf["limits"]["token_gap_mean"])]


def calibrate(cell, devs, seeds, control, faults=()):
    """For each seed: the served-token gaps of the program's answers to
    ``sample_requests`` requests; for seeds in ``control``, the int8
    reference's at the same positions."""
    import tempfile
    from harness.core import Run
    n = cell.traffic["sample_requests"]
    with tempfile.TemporaryDirectory(prefix="bench_") as work:
        run = Run(cell, seeds[0], 0, False, time.perf_counter(), work)
        run.devices = devs
        rep = Replica(run, seeds[0])
        for seed in seeds:
            rep.reseed(seed)
            params = rep.params()
            answers = {i: rep.answer(params, i) for i in range(1, n + 1)}
            del params
            yield {"seed": seed, "side": "program", **gap_readings(rep.token_gaps(answers))}
            if seed in control:
                yield {"seed": seed, "side": "control",
                       **gap_readings(rep.token_gaps(answers, mode="int8"))}
