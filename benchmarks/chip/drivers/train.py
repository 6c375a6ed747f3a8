"""Closed-loop training, as ``launch/train.train_loop`` composes it: the
compiled step, a host sync on each step's loss, and an async xDFS save
(``AsyncCheckpointer``) every ``ckpt_every`` steps, or none.

Traffic parameters: ``batch``, ``seq``, ``check_steps``, ``sync`` ("xla":
``jit_train_step``; "ring": ``make_dp_xdfs_train_step``), ``ckpt_every``
(0: no saves) and ``keep_last``.

The window runs whole periods of ``max(ckpt_every, 1)`` steps, each ended by
its save, and starts one while ``--seconds`` remain. End to end:
``train_tokens_per_s`` over all steps and all the window, stalls included;
``ckpt_commit_s``, the mean over saves from the save call to its future's
result, every save waited for after the window.

Checks: the first steps against the reference (set-up), every loss in the
window finite, and one committed checkpoint drawn from the seed restored and
compared bit for bit with the state that was saved.
"""
from __future__ import annotations

import math
import random
import statistics
import time

import jax

from harness import checks
from harness.checks import Check
from repro.checkpoint import xdfs_ckpt
from repro.checkpoint.async_ckpt import AsyncCheckpointer

from trainer import Trainer


def run(run):
    tr = Trainer(run)
    traffic, limits = tr.traffic, tr.conf["limits"]
    prog = tr.first_steps()
    every = traffic.get("ckpt_every", 0)
    period = max(every, 1)
    ckpt = None
    if every:
        ckdir = f"{run.workdir}/ckpt"
        ckpt = AsyncCheckpointer(ckdir, keep_last=traffic["keep_last"])
        jax.block_until_ready(checks.checksums(tr.state))   # compile it here
    saves, done, losses = [], {}, []
    i = tr.steps_done
    with run.window():
        t0 = time.perf_counter()
        while True:
            with run.span("steps"):
                for _ in range(period):
                    losses.append(tr.step(i))
                    i += 1
            if ckpt is not None:
                t_call = time.perf_counter()
                with run.span("ckpt_save"):
                    fut = ckpt.save(tr.state, i)
                # outside the save's span: the next step waits for it
                sums = checks.checksums(tr.state)
                fut.add_done_callback(
                    lambda f, i=i: done.__setitem__(i, time.perf_counter()))
                saves.append((i, t_call, sums))
            if time.perf_counter() - t0 >= run.seconds:
                break
    run.read_memory()
    steps = i - tr.steps_done
    run.attempted = steps
    run.e2e["train_tokens_per_s"] = steps * tr.tokens_per_step / run.window_s
    facts = run.facts
    facts.update(steps=steps, tokens_per_step=tr.tokens_per_step,
                 batch=tr.B, seq=tr.S, model=tr.m, saves=len(saves))
    chk = [Check("nonfinite_losses", float(sum(not math.isfinite(l) for l in losses)), 0.0)]
    if ckpt is not None:
        ckpt.close()   # waits for every save; raises what a save raised
        run.e2e["ckpt_commit_s"] = statistics.mean(done[s] - t for s, t, _ in saves)
        like, ss = tr.state_like(), tr.ss
        tr.drop()
        kept = [s for s in saves if s[0] in _committed(ckdir)]
        step, _, want = random.Random(run.seed).choice(kept) if kept else (None, 0, None)
        if step is None:
            chk.append(Check("ckpt_leaves_differ", float("inf"), 0.0))
        else:
            restored, got_step = xdfs_ckpt.restore(ckdir, like, step=step, shardings=ss)
            differ = checks.leaves_differ(checks.checksums(restored), want)
            chk.append(Check("ckpt_leaves_differ",
                             float(differ + (got_step != step)), 0.0))
            del restored
    tr.drop()
    ref = tr.reference_readings()
    run.checks = chk + checks.training_checks(prog, ref, limits)
    facts["readings"] = {"program": prog, "reference": ref}


def _committed(ckdir: str) -> set:
    from pathlib import Path
    return {int(p.name.split("_")[1]) for p in Path(ckdir).glob("step_*")
            if not p.name.endswith(".tmp") and (p / "manifest.json").exists()}


def calibrate(cell, devs, seeds, control, faults=()):
    """For each seed: the program's gaps to the reference, and, for seeds in
    ``control``, the int8 control's gaps to the same reference and those of
    each fault in ``faults`` planted in the reference."""
    import tempfile
    from harness.core import Run
    limits = cell.config["limits"]
    with tempfile.TemporaryDirectory(prefix="bench_") as work:
        run = Run(cell, seeds[0], 0, False, time.perf_counter(), work)
        run.devices = devs
        tr = Trainer(run)
        for seed in seeds:
            t0 = time.perf_counter()
            tr.reseed(seed)
            prog = tr.first_steps()
            tr.drop()
            t1 = time.perf_counter()
            ref = tr.reference_readings()
            t2 = time.perf_counter()
            yield _row(seed, "program", checks.training_checks(prog, ref, limits),
                       program_s=t1 - t0, reference_s=t2 - t1, readings=prog,
                       ref_readings=ref)
            if seed in control:
                ctl = tr.reference_readings(mode="int8")
                yield _row(seed, "control", checks.training_checks(ctl, ref, limits),
                           control_s=time.perf_counter() - t2, readings=ctl)
                for fault in faults:
                    bad = tr.reference_readings(fault=fault)
                    yield _row(seed, fault, checks.training_checks(bad, ref, limits))


def _row(seed, side, chk, **extra):
    return {"seed": seed, "side": side, **{c.name: c.value for c in chk}, **extra}
