"""The yardstick's arithmetic: FLOPs, peaks, checksums, gaps, reference."""
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import checks, core, data, device, reference

CONFIGS = Path(__file__).resolve().parents[1] / "configs"
SMOLLM = json.loads((CONFIGS / "smollm-135m-train.json").read_text())["model"]


def test_flops_match_a_hand_count_for_smollm_135m():
    ref = _ref()
    # per layer: q 576x576, k and v 576x192, o 576x576, MLP 3 x 576x1536
    per_layer = 576 * 576 * 2 + 576 * 192 * 2 + 3 * 576 * 1536
    n = 30 * per_layer + 576 * 49152          # head tied to the embedding
    assert ref.matmul_params(SMOLLM) == n == 134_479_872
    dense = 6 * n * 8 * 2048                  # 13.2 TFLOP
    # causal QK^T and PV, forward 2 * 2 * S^2/2 * 64 per head and sequence,
    # backward twice that: 3.48 TFLOP
    attn = 3 * 2 * 2 * (2048 ** 2 / 2) * 64 * 9 * 8 * 30
    assert dense == pytest.approx(13.22e12, rel=1e-3)
    assert attn == pytest.approx(3.479e12, rel=1e-3)
    assert ref.train_flops(SMOLLM, 8, 2048) == pytest.approx(dense + attn)


@pytest.mark.parametrize("name", ["smollm-135m.train-ckpt", "smollm-135m.dp4-ring"])
def test_step_mfu_reads_the_smollm_count_as_before(name):
    # 16,698,832,846,848 FLOP per 8 x 2048 step: the count step_mfu read
    # from harness/flops.py before each architecture's count moved to its
    # reference; equal to the last bit
    cell = core.find_cell(name)
    reader = core.load_module(core.HERE / "metrics" / "step_mfu.py", "bench_metric_step_mfu")
    kind = SimpleNamespace(device_kind="TPU v5 lite")
    run = SimpleNamespace(cell=cell, window_s=2.0, devices=[kind] * cell.chips,
                          facts={"steps": 3, "model": SMOLLM, "batch": 8, "seq": 2048})
    assert reader.read(run) == 100 * (16698832846848.0 * 3) / (2.0 * cell.chips * 197e12)


def test_peaks_refuse_an_unknown_device_kind():
    assert device.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


def test_no_chip_is_refused():
    with pytest.raises(device.NoChip):
        device.require_chips(1)
    with pytest.raises(device.NoChip):
        device.require_chips(64, allow_cpu=True)


def test_checksums_see_any_one_word_changed_or_moved():
    tree = {"a": jnp.arange(1000, dtype=jnp.float32).reshape(10, 100),
            "b": jnp.ones((7, 3), jnp.bfloat16)}
    base = checks.checksums(tree)
    assert checks.leaves_differ(base, checks.checksums(tree)) == 0
    flipped = dict(tree, b=tree["b"].at[3, 1].set(1.0078125))
    assert checks.leaves_differ(base, checks.checksums(flipped)) == 1
    swapped = dict(tree, a=tree["a"].at[0, :2].set(tree["a"][0, 1::-1]))
    assert checks.leaves_differ(base, checks.checksums(swapped)) == 1


def test_gaps():
    assert checks.loss_gap([2.0, 4.0], [2.0, 5.0]) == pytest.approx(0.2)
    ref = {"a": 1.0, "b": 2.0, "c": 100.0}
    # leaf a is measured against the median leaf's norm, 2
    assert checks.norm_gap({"a": 1.5, "b": 2.0, "c": 100.0}, ref) == pytest.approx(0.25)
    assert checks.moving_leaves({"a": 1e-9, "b": 1.0, "c": 2.0}) == {"b", "c"}
    prog = {"losses": [1.0], "grad_norms": ref, "change_norms": ref}
    limits = {"loss_gap": 0.1, "grad_gap": 0.1, "change_gap": 0.1}
    assert all(c.ok for c in checks.training_checks(prog, prog, limits))
    unchanged = dict(prog, change_norms={k: 0.0 for k in ref})
    assert not all(c.ok for c in checks.training_checks(unchanged, prog, limits))


def test_seed_keys_take_seeds_past_32_bits():
    a = data.tokens(data.seed_key(2**31 + 5, "train"), (64,), 1000)
    b = data.tokens(data.seed_key(2**31 + 5 + 2**32, "train"), (64,), 1000)
    c = data.tokens(data.seed_key(2**31 + 5, "train"), (64,), 1000)
    assert not np.array_equal(a, b) and np.array_equal(a, c)
    assert int(a.min()) >= 0 and int(a.max()) < 1000


def _ref():
    import llama_ref
    return llama_ref


TINY = dict(SMOLLM, hidden_size=32, intermediate_size=48, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, vocab_size=64)


def test_reference_blocks_do_not_change_the_mathematics():
    ref = _ref()
    p = jax.tree.map(lambda w: w.astype(jnp.float32),
                     ref.init(jax.random.key(1), TINY))
    toks = data.tokens(jax.random.key(2), (3, 9), 64)
    batch = {"inputs": toks[:, :-1], "labels": toks[:, 1:]}
    loss, grads = reference.loss_and_grads(ref.logits, p, batch, TINY)

    def whole(p):
        lg = jax.vmap(lambda x: ref.logits(p, x, TINY))(batch["inputs"])
        lz = jax.nn.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, batch["labels"][..., None], -1)[..., 0]
        return jnp.mean(lz - gold)

    want, wgrads = jax.value_and_grad(whole)(p)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    for k in grads:
        np.testing.assert_allclose(grads[k], wgrads[k], rtol=1e-4, atol=1e-7)


def test_reference_attention_is_causal():
    ref = _ref()
    p = jax.tree.map(lambda w: w.astype(jnp.float32),
                     ref.init(jax.random.key(1), TINY))
    toks = data.tokens(jax.random.key(3), (10,), 64)
    a = ref.logits(p, toks, TINY)
    b = ref.logits(p, toks.at[7:].set(0), TINY)
    np.testing.assert_allclose(a[:7], b[:7], rtol=1e-6)
    assert not np.allclose(a[7:], b[7:])


def test_run_refuses_the_cpu_and_prints_no_result():
    import os
    import subprocess
    import sys
    run = Path(__file__).resolve().parents[1] / "run.py"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(run), "--workload", "smollm-135m.train-ckpt",
                        "--seed", str(2**31 + 1), "--seconds", "1"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode == 2 and p.stdout == "", (p.returncode, p.stdout, p.stderr[-500:])
    assert "needs a TPU" in p.stderr
