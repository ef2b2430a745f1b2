"""The examples are functional baselines (BASELINE.json "configs"): each
job.toml must run green through the mini cluster, TestTonyE2E-style —
the job's exit status is the assertion.

Reference analog: tony-examples/* exercised in docs; here promoted to CI.
"""

import os

import pytest

from tony_tpu.config import build_conf
from tony_tpu.mini import MiniTonyCluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


@pytest.fixture
def cluster():
    with MiniTonyCluster() as c:
        yield c


def example_conf(cluster, name, **overrides):
    conf = cluster.adopt(build_conf(os.path.join(EXAMPLES, name, "job.toml")))
    # resolve the entrypoint relative to the repo root
    conf.set("tony.application.executes",
             os.path.join(REPO, str(conf.get("tony.application.executes"))))
    for k, v in overrides.items():
        conf.set(k, v)
    return conf


def test_linear_regression_example(cluster):
    client = cluster.submit(example_conf(cluster, "linear-regression"))
    assert client.final_status["status"] == "SUCCEEDED", client.final_status


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_mnist_jax_example(cluster):
    conf = example_conf(
        cluster, "mnist-jax",
        **{"tony.application.task-params": "--steps 8 --global-batch 64"})
    client = cluster.submit(conf)
    assert client.final_status["status"] == "SUCCEEDED", client.final_status


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_mnist_pytorch_example(cluster):
    conf = example_conf(
        cluster, "mnist-pytorch",
        **{"tony.application.task-params": "--steps 8 --batch 64"})
    client = cluster.submit(conf)
    assert client.final_status["status"] == "SUCCEEDED", client.final_status


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_lm_pretrain_example(cluster):
    """Full-stack flagship: loader + GQA/chunked-CE + fit with checkpoints,
    2-worker gang."""
    conf = example_conf(
        cluster, "lm-pretrain",
        # batch divisible by the gang's global device count (2 procs x 8
        # forced host devices in the test env = 16)
        **{"tony.application.task-params":
           "--steps 6 --global-batch 16 --seq-len 32 --vocab 64"})
    client = cluster.submit(conf)
    assert client.final_status["status"] == "SUCCEEDED", client.final_status
    # the coordinator archives fit()'s metric sink into history for the
    # portal's /metrics page
    import glob

    hist = str(conf.get("tony.history.location"))
    archived = glob.glob(os.path.join(
        hist, "**", client.app_id, "metrics", "train.jsonl"), recursive=True)
    assert archived, f"metrics not archived under {hist}"


def test_ray_example(cluster):
    client = cluster.submit(example_conf(cluster, "ray-on-tony"))
    assert client.final_status["status"] == "SUCCEEDED", client.final_status


def test_horovod_example(cluster):
    client = cluster.submit(example_conf(cluster, "horovod-on-tony"))
    assert client.final_status["status"] == "SUCCEEDED", client.final_status


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_examples_run_standalone():
    """The documented degrade-gracefully contract: every example script
    exits 0 outside a gang."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for rel, args in [
        ("linear-regression/linreg.py", []),
        ("horovod-on-tony/mnist_hvd.py", []),
        ("ray-on-tony/example.py", []),
        ("mnist-pytorch/mnist_ddp.py", ["--steps", "8", "--batch", "64"]),
        ("mnist-jax/mnist_spmd.py", ["--steps", "8", "--global-batch", "64"]),
        ("lm-pretrain/pretrain.py", ["--steps", "6", "--global-batch", "8",
                                     "--seq-len", "32", "--vocab", "64",
                                     "--moe"]),
        ("sft-lora/finetune.py", ["--steps", "120"]),
    ]:
        entry_env = dict(env)
        if rel.startswith("sft-lora"):
            # single device: no virtual mesh -> no CPU collective
            # rendezvous to stall on this loaded 1-core box. Replace only
            # the device-count flag; keep any other inherited XLA flags.
            kept = [f for f in entry_env.get("XLA_FLAGS", "").split()
                    if "xla_force_host_platform_device_count" not in f]
            entry_env["XLA_FLAGS"] = " ".join(
                kept + ["--xla_force_host_platform_device_count=1"])
        proc = subprocess.run(
            [sys.executable, os.path.join(EXAMPLES, rel), *args],
            env=entry_env, capture_output=True, text=True, timeout=180)
        assert proc.returncode == 0, (rel, proc.stdout, proc.stderr)


def test_tpu_pod_conf_selects_ssh_launcher():
    """launch-mode=ssh must reach the SshLauncher (not silently fall back
    to local subprocesses)."""
    from tony_tpu.coordinator.coordinator import Coordinator
    from tony_tpu.coordinator.launcher import SshLauncher
    import tempfile

    conf = build_conf(os.path.join(EXAMPLES, "tpu-pod", "job.toml"))
    conf.set("tony.application.hosts", "h1,h2")
    conf.set("tony.application.security.enabled", False)
    with tempfile.TemporaryDirectory() as tmp:
        conf.set("tony.staging-dir", tmp)
        conf.set("tony.history.location", os.path.join(tmp, "hist"))
        coord = Coordinator(conf, "application_test_ssh", os.path.join(tmp, "job"))
        try:
            assert isinstance(coord.launcher, SshLauncher)
            assert coord.launcher.hosts == ["h1", "h2"]
        finally:
            coord.rpc.stop()
            coord.metrics_rpc.stop()


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_lm_pretrain_on_raw_text(tmp_path):
    """--text: raw files -> byte-tokenized packed corpus -> fit, standalone
    (no cluster; the data-prep path is what's under test)."""
    import subprocess
    import sys

    corpus = tmp_path / "corpus.txt"
    corpus.write_text("the quick brown fox jumps over the lazy dog. " * 100)
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "lm-pretrain", "pretrain.py"),
         "--steps", "4", "--global-batch", "8", "--seq-len", "32",
         "--text", str(corpus)],
        capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=str(tmp_path))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "tokenized 1 file(s)" in proc.stdout


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_sft_lora_example(cluster):
    """Post-training flagship: InstructionSource masked loss + frozen base
    + LoRA adapters; the script's own greedy-decode check is the exit
    status."""
    conf = example_conf(
        cluster, "sft-lora",
        **{"tony.application.task-params": "--steps 120 --global-batch 8",
           # single-device worker: CPU collective rendezvous on this
           # loaded 1-core box times out sporadically; SPMD coverage
           # lives in the parallel/e2e suites, this test asserts the
           # SFT+LoRA pipeline
           "tony.application.shell-env":
           "XLA_FLAGS=--xla_force_host_platform_device_count=1"})
    client = cluster.submit(conf)
    assert client.final_status["status"] == "SUCCEEDED", client.final_status
