"""A run with its timed path broken underneath must come out not correct:
once for each fault a cell can have. Tiny sizes on the CPU, past the
harness's look for a chip; the limits are the configurations' own."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tiny import rehearse, tiny_cell

TRAIN_CELLS = ["smollm-135m.train-ckpt", "smollm-135m.resume", "smollm-135m.dp4-ring"]


def _trainer():
    cell = tiny_cell(TRAIN_CELLS[0])
    cell.driver()                     # puts drivers/ on the path
    import trainer
    return trainer


def _break_step(monkeypatch, wrap):
    """Wrap every step the trainer builds in ``wrap(real_step)``."""
    tr = _trainer()
    for name in ("jit_train_step", "make_dp_xdfs_train_step"):
        real = getattr(tr, name)
        monkeypatch.setattr(tr, name, lambda *a, _r=real, **k: wrap(_r(*a, **k)))


def _state_unchanged(step):
    def f(state, batch):
        _, metrics = step(jax.tree.map(jnp.copy, state), batch)
        return state, metrics
    return f


def _half_batch(step):
    def f(state, batch):
        return step(state, jax.tree.map(lambda x: x[: x.shape[0] // 2], batch))
    return f


def _failed(line) -> list:
    return [k for k, c in line["checks"].items() if not c["value"] <= c["limit"]]


@pytest.mark.parametrize("name", TRAIN_CELLS)
@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch])
def test_broken_step_is_not_correct(name, fault, monkeypatch):
    _break_step(monkeypatch, fault)
    line = rehearse(tiny_cell(name))
    assert not line["correct"] and _failed(line), line["checks"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    from repro.core import channel
    monkeypatch.setattr(channel, "xdfs_psum_tree", lambda g, ax, compress=False: g)
    line = rehearse(tiny_cell("smollm-135m.dp4-ring"))
    assert not line["correct"] and "grad_gap" in _failed(line), line["checks"]


def _alter(tree):
    leaves, treedef = jax.tree.flatten(tree)
    leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1)
    return jax.tree.unflatten(treedef, leaves)


def test_checkpoint_altered_where_written_is_not_correct(monkeypatch):
    from repro.checkpoint.async_ckpt import AsyncCheckpointer
    real = AsyncCheckpointer.save
    monkeypatch.setattr(AsyncCheckpointer, "save",
                        lambda self, tree, step: real(self, _alter(tree), step))
    line = rehearse(tiny_cell("smollm-135m.train-ckpt"))
    assert _failed(line) == ["ckpt_leaves_differ"], line["checks"]


def test_restore_altered_is_not_correct(monkeypatch):
    from repro.checkpoint import xdfs_ckpt
    real = xdfs_ckpt.restore

    def restore(*a, **k):
        state, step = real(*a, **k)
        return _alter(state), step
    monkeypatch.setattr(xdfs_ckpt, "restore", restore)
    for name in ("smollm-135m.resume", "smollm-135m.serve-cold"):
        line = rehearse(tiny_cell(name))
        assert not line["correct"], line["checks"]
        assert set(_failed(line)) & {"cycles_differ", "restores_differ"}


def test_served_token_altered_is_not_correct(monkeypatch):
    cell = tiny_cell("smollm-135m.serve-cold")
    drv = cell.driver()
    real = drv.generate

    def generate(cfg, mesh, params, prompts, gen, **k):
        toks = np.array(real(cfg, mesh, params, prompts, gen, **k))
        # the reference's least likely token in the tiny vocabulary's tail
        toks[0, 0] = cfg.vocab_size - 1
        return toks
    monkeypatch.setattr(drv, "generate", generate)
    line = rehearse(cell, driver=drv)
    assert "token_gap_mean" in _failed(line), line["checks"]
