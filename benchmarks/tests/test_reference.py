"""The plain reference against the system under test at toy sizes, the
control against the reference, and whole rehearsal runs — sound, and with
the timed path broken underneath."""

import json
import math
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CONFIGS = ("mistral-7b-v0.3-l10", "pythia-1.4b-l6")
PINS = json.load(open(os.path.join(ROOT, "benchmarks", "tests", "pins.json")))


def toy(name):
    from benchmarks.harness import weights as W

    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        return W.arch(json.load(f), rehearsal=True)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_program_at_toy_size(name):
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import adapter as A, reference as R, weights as W
    from tony_tpu.models import Transformer

    a, seed = toy(name), 2**31 + 11
    params = A.seeded_params(a, seed, jnp.float32)
    model = Transformer(A.program_config(a, jnp.float32,
                                         attention_backend="reference"))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    assert jax.tree.structure(shapes) == jax.tree.structure(params)
    assert [x.shape for x in jax.tree.leaves(shapes)] == \
        [x.shape for x in jax.tree.leaves(params)]
    # a leaf made alone is the leaf made inside the whole model
    alone = jax.jit(lambda: W.leaf(a, seed, 1, "q", jnp.float32))()
    assert (np.asarray(alone)
            == np.asarray(params["block_1"]["attn"]["q"]["kernel"])).all()
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                         a.vocab))
    logits = np.asarray(model.apply({"params": params}, jnp.asarray(toks)))
    pos = np.tile(np.arange(48, dtype=np.int32)[None], (2, 1))
    served = logits.argmax(-1).astype(np.int32)
    best, at, _ = R.serve_logits(a, seed, toks, pos, served,
                                 dtype=jnp.float32)
    assert np.abs(np.asarray(best) - logits.max(-1)).max() < 1e-5
    assert float(jnp.max(best - at)) < 1e-5


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_and_reference_are_what_they_were_before_the_families_moved(
        name):
    """Taken on PR 29's parent: every leaf's sum and sum of squares
    (exact: ``fsum`` over float64), so the same seed still makes
    bit-identical weights (a leaf's place in its family's list is folded
    into its key); and the plain reference's readings of them, to
    float32 rounding."""
    import jax
    import jax.numpy as jnp

    from benchmarks.harness import reference as R, weights as W

    a, seed = toy(name), PINS["seed"]
    now = {}
    for layer, specs in ((1, W.layer_leaves(a, 1)), (-1, W.global_leaves(a))):
        for n, _, _ in specs:
            x = np.asarray(W.leaf(a, seed, layer, n, jnp.float32),
                           np.float64).ravel()
            now[f"{layer}/{n}"] = [math.fsum(x), math.fsum(x * x)]
    assert now == PINS["leaves"][name]
    was = PINS["reference"][name]
    toks = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0,
                                         a.vocab))
    pos = np.tile(np.arange(48, dtype=np.int32)[None], (2, 1))
    best, at, argmax = R.serve_logits(
        a, seed, toks, pos, (toks[:, ::-1] % a.vocab).astype(np.int32))
    assert float(np.asarray(best, np.float64).sum()) == pytest.approx(
        was["serve_best_sum"], rel=1e-6)
    assert float(np.asarray(at, np.float64).sum()) == pytest.approx(
        was["serve_at_sum"], rel=1e-5)
    assert int(np.asarray(argmax).sum()) == was["serve_argmax_sum"]
    with open(os.path.join(ROOT, "benchmarks", "traffic", "pretrain-2k.json")) as f:
        job = json.load(f)
    job.update(job["rehearsal"])
    assert R.train_reference(a, seed, job, 3)["losses"] == pytest.approx(
        was["train_losses"], rel=1e-6)


def test_int8_control_reads_above_the_bf16_program_serving():
    """The control (the reference in int8, in the program's place) reads
    at least three times what bf16 arithmetic reads, by the cell's own
    number, at a size a test can hold."""
    from benchmarks.harness.control import serve_control

    a, seed = toy("mistral-7b-v0.3-l10"), 7
    rng = np.random.default_rng(0)
    # any tokens do: the control reads the token the int8 reference puts
    # first at each position of the rows it is given
    rows = [[p[:9], p[9:]] for p in
            (rng.integers(1, a.vocab, 40).tolist() for _ in range(6))]
    low = serve_control(a, seed, rows)
    assert low["logit_gap_max"] > 0 and low["argmax_is_float32_argmax"] < 1


def rehearse(workload, *extra):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", workload, "--seed", str(2**31 + 5), "--seconds", "4",
         "--rehearsal", *extra], capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.splitlines()[-1])


def test_fp8_control_and_half_batch_fail_the_training_numbers():
    """At a size a test can hold: the reference in fp8 reads, by the
    cell's first-order number, three times what the bf16 program reads
    or more; half of the batch left out reads ten times the program's
    gradient-norm gap or more."""
    from benchmarks.harness.control import train_readings

    a = toy("pythia-1.4b-l6")
    with open(os.path.join(ROOT, "benchmarks", "traffic", "pretrain-2k.json")) as f:
        job = json.load(f)
    job.update(job["rehearsal"])
    seed = 2**31 + 5
    sound = {k: v["value"] for k, v in rehearse(
        "pythia-1.4b.pretrain-2k")["checks"].items()}
    low = train_readings(a, seed, job, quant="fp8")
    half = train_readings(a, seed, job, rows=2)
    assert low["grad_diff_worst_leaf"] >= 3 * sound["grad_diff_worst_leaf"]
    assert half["grad_norm_worst_leaf"] >= 10 * sound["grad_norm_worst_leaf"]
    assert half["grad_diff_worst_leaf"] >= 10 * sound["grad_diff_worst_leaf"]


@pytest.mark.parametrize("workload,fault", [
    ("mistral-7b.chat-steady", ""), ("mistral-7b.chat-steady", "token"),
    ("pythia-1.4b.pretrain-2k", ""), ("pythia-1.4b.pretrain-2k", "unchanged"),
    ("pythia-1.4b.pretrain-2k", "half_batch")])
def test_a_rehearsal_run_is_never_a_result_and_sees_a_planted_fault(
        workload, fault):
    line = rehearse(workload, *(["--fault", fault] if fault else []))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert line["correct"] is False
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert line["rehearsal"]["checks_pass"] is (fault == ""), line["checks"]


def test_a_traced_rehearsal_prints_per_layer_metrics():
    line = rehearse("mistral-7b.chat-steady", "--trace", "1")
    assert "queue_wait_p50_ms" in line["metrics"]
    assert "decode_dispatch_host_ms" in line["metrics"]
    # no device plane on the CPU: shares of a peak are left out, never 0
    assert "decode_hbm_roofline_pct" not in line["metrics"]


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "pythia-1.4b.pretrain-2k", "--seconds", "1"],
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and out.stdout.strip() == ""
