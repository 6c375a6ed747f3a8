"""Resume after a fault, over and over: drop the live train state, restore
the newest committed step through a local xDFS session
(``xdfs_ckpt.restore`` with the state's shardings), run one step.

Traffic parameters: ``batch``, ``seq``, ``check_steps``, ``sync``,
``keep_last``. Set-up trains ``check_steps`` steps (checked against the
reference), commits one checkpoint through ``AsyncCheckpointer``, and runs
one cycle to warm up. The window starts cycles while ``--seconds`` remain
and runs each to its end. End to end: ``resume_s``, the mean over cycles
from the drop to the step's loss on the host.

Checks: the first steps against the reference, and in every cycle the
restored state bit for bit the saved one and the loss after it the same as
in the warm-up cycle.
"""
from __future__ import annotations

import math
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
    prog = tr.first_steps()
    ckdir = f"{run.workdir}/ckpt"
    ckpt = AsyncCheckpointer(ckdir, keep_last=tr.traffic["keep_last"])
    want = checks.checksums(tr.state)
    ckpt.save(tr.state, tr.steps_done)
    ckpt.close()
    like, ss, n_bytes = tr.state_like(), tr.ss, tr.state_bytes()

    def cycle():
        tr.drop()   # the fault: the live state is gone
        t0 = time.perf_counter()
        with run.span("restore"):
            state, step = xdfs_ckpt.restore(ckdir, like, shardings=ss)
            jax.block_until_ready(state)
        tr.state = state
        # inside resume_s, since the step donates the state: one pass over
        # the state on the device, milliseconds against a cycle of seconds
        sums = checks.checksums(state)
        with run.span("step"):
            loss = tr.step(step)
        return time.perf_counter() - t0, sums, loss

    _, base_sums, base_loss = cycle()
    differ = checks.leaves_differ(base_sums, want)
    times, results = [], []
    with run.window():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < run.seconds:
            dt, sums, loss = cycle()
            times.append(dt)
            results.append((sums, loss))
    run.read_memory()
    run.attempted = len(times)
    run.e2e["resume_s"] = statistics.mean(times)
    run.facts.update(restored_bytes=n_bytes, cycles=len(times))
    bad = sum(checks.leaves_differ(s, want) > 0 or l != base_loss for s, l in results)
    tr.drop()
    ref = tr.reference_readings()
    run.checks = [Check("cycles_differ", float(bad + differ), 0.0),
                  Check("nonfinite_losses", float(not math.isfinite(base_loss)), 0.0)] \
        + checks.training_checks(prog, ref, tr.conf["limits"])
    run.facts["readings"] = {"program": prog, "reference": ref}
