"""chip_smoke.py's parent, rehearsed without a chip, and the rule it
exists to keep: one process per chip.

The parent never imports jax and turns every way a phase can go wrong —
a failed check, a platform other than ``tpu``, the wrong device count, a
time-out, no record at all — into a non-zero exit and a last line that
is not ``"ok": true``. Phases here are fakes (a ``python -c`` each), so
no test needs a device. The end of the file checks the processes that
must stay off the chip so that another can have it: the submit client,
the coordinator, the agent, and a router-only gateway.
"""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TPU = {"ok": True, "platform": "tpu", "device_kind": "TPU v5 lite",
       "device_count": 1}


def fake(name: str, record: dict | None, *, rc: int = 0, before: str = ""):
    """A phase that prints ``record`` (tagged with its name) and exits
    ``rc``; ``before`` is python run first."""
    code = before + "\nimport json, sys\n"
    if record is not None:
        code += f"print(json.dumps({dict(record, phase=name)!r}))\n"
    code += f"sys.exit({rc})\n"
    return name, [sys.executable, "-c", code], 30.0


def run(capsys, phases, **kw):
    rc = chip_smoke.run_phases(phases, **kw)
    lines = capsys.readouterr().out.strip().splitlines()
    return rc, [json.loads(ln) for ln in lines if ln.startswith("{")]


def test_parent_module_does_not_import_jax():
    code = ("import sys; sys.path.insert(0, %r); import chip_smoke; "
            "bad = [m for m in ('jax', 'jaxlib', 'numpy', 'tony_tpu') "
            "if m in sys.modules]; sys.exit(repr(bad) if bad else 0)" % REPO)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0, out.stderr


def test_all_phases_on_a_tpu_give_the_contract_line(capsys):
    rc, docs = run(capsys, [fake("serve", TPU), fake("train", TPU)], chips=1)
    assert rc == 0
    assert [d.get("phase") for d in docs[:-1]] == ["serve", "train"]
    assert docs[-1] == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}


@pytest.mark.parametrize("bad, why", [
    (fake("train", dict(TPU, ok=False, error="check failed: x"), rc=3),
     "check failed"),
    (fake("train", dict(TPU, platform="cpu", device_kind="cpu")),
     "not a TPU"),
    (fake("train", dict(TPU, device_count=4)), "asked for 1"),
    (fake("train", None), "no record"),
    (fake("train", TPU, rc=1), "exit code 1"),
    (fake("train", None, rc=-9, before="import os, signal; "
          "os.kill(os.getpid(), signal.SIGKILL)"), "no record"),
], ids=["failed-check", "reports-cpu", "wrong-device-count", "no-record",
        "nonzero-exit", "killed"])
def test_a_bad_phase_fails_the_script(capsys, bad, why):
    rc, docs = run(capsys, [fake("serve", TPU), bad], chips=1)
    assert rc == 1
    assert docs[-1] == {"ok": False, "failed": ["train"]}
    assert why in docs[-2]["error"], docs[-2]
    assert not any(d.get("ok") is True and "phase" not in d for d in docs)


def test_a_timed_out_phase_fails_and_leaves_no_process(capsys, tmp_path):
    """The phase hangs after starting a child of its own in a NEW
    session (as the agent starts the user process): both are gone when
    the parent moves on."""
    pidfile = tmp_path / "grandchild.pid"
    before = (
        "import subprocess, sys, time\n"
        "p = subprocess.Popen([sys.executable, '-c', 'import time; "
        "time.sleep(600)'], start_new_session=True)\n"
        f"open({str(pidfile)!r}, 'w').write(str(p.pid))\n"
        "time.sleep(600)\n")
    name, argv, _ = fake("serve", TPU, before=before)
    t0 = time.monotonic()
    rc, docs = run(capsys, [(name, argv, 3.0), fake("train", TPU)], chips=1)
    assert rc == 1 and time.monotonic() - t0 < 60
    assert docs[-1] == {"ok": False, "failed": ["serve"]}
    assert "timed out" in docs[0]["error"] and docs[0]["exit_code"] is None
    grandchild = int(pidfile.read_text())
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        try:
            os.kill(grandchild, 0)
        except ProcessLookupError:
            break
        with open(f"/proc/{grandchild}/stat") as f:
            if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                break  # killed; only init has yet to reap it
        time.sleep(0.1)
    else:
        pytest.fail("the phase's own child outlived the time-out")


def test_the_whole_budget_bounds_the_phases(capsys):
    rc, docs = run(capsys, [fake("serve", TPU), fake("train", TPU)],
                   chips=1, budget_s=0.0)
    assert rc == 1
    assert docs[-1] == {"ok": False, "failed": ["serve", "train"]}
    assert docs[0]["error"] == "no time left"


def test_a_rehearsal_passes_but_is_never_a_result(capsys):
    cpu = dict(TPU, platform="cpu", device_kind="cpu", device_count=8)
    rc, docs = run(capsys, [fake("serve", cpu), fake("train", cpu)],
                   chips=1, rehearsal=True)
    assert rc == 0
    assert docs[-1]["ok"] is False and docs[-1]["rehearsal"] is True
    assert docs[-1]["passed"] is True
    assert docs[-1]["device"]["platform"] == "cpu"


def test_without_a_chip_the_script_fails_and_does_not_carry_on():
    """The real script, real phases, on this CPU-only box: the serve
    child refuses the CPU at its first question to jax, the train
    payload — pinned to ``JAX_PLATFORMS=tpu`` — dies at start-up inside
    its job, and nothing reports ``"ok": true``."""
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=600, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 1, out.stderr[-3000:]
    docs = [json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")]
    assert docs[-1] == {"ok": False, "failed": ["serve", "train"]}
    assert '"ok": true' not in out.stdout
    serve, train = (next(d for d in docs if d.get("phase") == p)
                    for p in ("serve", "train"))
    assert serve["platform"] == "cpu" and "no TPU" in serve["error"]
    assert "submit exited" in train["error"]
    assert time.monotonic() - t0 < 300  # it failed; it did not train


# ------------------------------------------ who may open the chip

def test_submit_client_coordinator_and_agent_never_start_a_backend(
        tmp_path):
    """The control plane runs with a platform jax does not know, so any
    backend start-up in the client, the coordinator or the agent would
    raise; the user process alone is handed a real one through
    ``tony.application.shell-env``. The job still succeeds."""
    out = subprocess.run(
        [sys.executable, "-m", "tony_tpu.cli.submit",
         "--framework", "jax", "--executes",
         os.path.join(REPO, "tests", "scripts", "check_jax_env.py"),
         "--shell_env", "JAX_PLATFORMS=cpu",
         "--conf", "tony.worker.instances=1",
         "--conf", f"tony.staging-dir={tmp_path}/staging",
         "--conf", f"tony.history.location={tmp_path}/history",
         "--conf", "tony.client.poll-interval-ms=100",
         "--conf", "tony.task.heartbeat-interval-ms=100",
         "--conf", "tony.coordinator.monitor-interval-ms=100"],
        capture_output=True, text=True, timeout=300, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="no_such_backend",
                 PYTHONPATH=REPO))
    assert out.returncode == 0, (out.stdout + out.stderr)[-3000:]
    assert "SUCCEEDED" in out.stderr + out.stdout


def test_router_only_gateway_never_starts_a_backend(tmp_path):
    """``--agents`` makes the gateway a pure router: the agent (here a
    CPU one) owns the device. The router serves a request, drains on
    SIGTERM with exit code 0, and at no point has jax started a
    backend in it — asked of jax itself as the process exits."""
    import urllib.request

    from tony_tpu.gateway.remote import launch_local_agent

    agent, addr = launch_local_agent(
        ["--demo-model", "--serve-batch", "2", "--port", "0",
         "--replica-index", "0", "--compile-cache", ""],
        port_file=str(tmp_path / "agent.port"), boot_timeout_s=180.0)
    router_code = (
        "import sys\n"
        "from tony_tpu.cli import gateway\n"
        "rc = gateway.main(sys.argv[1:])\n"
        "xb = sys.modules.get('jax._src.xla_bridge')\n"
        "print('BACKENDS_STARTED', bool(xb and "
        "xb.backends_are_initialized()), flush=True)\n"
        "sys.exit(rc)\n")
    router = subprocess.Popen(
        [sys.executable, "-c", router_code, "--agents", addr, "--port", "0",
         "--agent-heartbeat", "0.2"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="no_such_backend",
                 PYTHONPATH=REPO))
    try:
        url = None
        deadline = time.monotonic() + 120
        while url is None and time.monotonic() < deadline:
            line = router.stdout.readline()
            if not line:
                break
            if "gateway at " in line:
                url = line.split("gateway at ")[1].split()[0]
        assert url, router.stderr.read()[-3000:]
        req = urllib.request.Request(
            url + "/v1/generate",
            data=json.dumps({"token_ids": [1, 2, 3],
                             "max_new_tokens": 4}).encode())
        with urllib.request.urlopen(req, timeout=180) as r:
            doc = json.loads(r.read())
        assert r.status == 200 and len(doc["token_ids"]) == 7
        router.send_signal(signal.SIGTERM)
        out, err = router.communicate(timeout=120)
        assert router.returncode == 0, err[-3000:]
        assert "BACKENDS_STARTED False" in out, (out, err[-2000:])
    finally:
        for p in (router, agent):
            if p.poll() is None:
                p.kill()
            p.wait(timeout=30)
