"""Profiler subsystem tests (greenfield vs reference; SURVEY.md §5.1)."""

import glob
import json
import os

import pytest

import jax.numpy as jnp

from tony_tpu.profiler import StepProfiler, trigger_path, write_trigger


def test_trigger_roundtrip(tmp_path):
    path = write_trigger(str(tmp_path), num_steps=3, task_id="worker:1")
    assert path == trigger_path(str(tmp_path), "worker:1")
    with open(path) as f:
        assert json.load(f)["num_steps"] == 3
    # per-task isolation: a different task's poller must not see it
    assert not os.path.exists(trigger_path(str(tmp_path), "worker:0"))


def test_step_profiler_captures_trace(tmp_path):
    prof = StepProfiler(workdir=str(tmp_path), task_id="worker:0")
    assert prof.poll() is False  # idle poll is cheap + false
    write_trigger(str(tmp_path), num_steps=2, task_id="worker:0",
                  logdir=str(tmp_path / "prof"))
    for _ in range(4):
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
        prof.poll()
    assert prof.captures == 1
    assert prof.active_steps_left == 0
    # trigger consumed; xplane artifacts written
    assert not os.path.exists(trigger_path(str(tmp_path), "worker:0"))
    artifacts = glob.glob(str(tmp_path / "prof" / "**" / "*"), recursive=True)
    assert any(os.path.isfile(a) for a in artifacts), artifacts


def test_step_profiler_ignores_foreign_trigger(tmp_path):
    prof = StepProfiler(workdir=str(tmp_path), task_id="worker:0")
    write_trigger(str(tmp_path), num_steps=1, task_id="worker:1")
    assert prof.poll() is False
    assert prof.captures == 0


def test_coordinator_command_queue():
    """request_profile -> queued -> drained exactly once on heartbeat."""
    import tempfile

    from tony_tpu.config import TonyConf
    from tony_tpu.coordinator.coordinator import ClientRpcHandler, Coordinator

    conf = TonyConf()
    conf.set("tony.worker.instances", 1)
    conf.set("tony.application.security.enabled", False)
    with tempfile.TemporaryDirectory() as tmp:
        conf.set("tony.staging-dir", tmp)
        conf.set("tony.history.location", os.path.join(tmp, "hist"))
        coord = Coordinator(conf, "application_cmdq", os.path.join(tmp, "job"))
        try:
            handler = ClientRpcHandler(coord)
            assert handler.request_profile("worker:0", 7) is True
            assert handler.request_profile("ghost:9", 1) is False
            resp = handler.task_executor_heartbeat("worker:0")
            assert resp["commands"] == [{"type": "profile", "num_steps": 7}]
            # drained: second heartbeat is empty
            assert handler.task_executor_heartbeat("worker:0")["commands"] == []
        finally:
            coord.rpc.stop()
            coord.metrics_rpc.stop()


# ------------------------------------------------------- xplane parsing


def test_xplane_parse_cpu_trace(tmp_path):
    """On the CPU backend the trace has host planes but no /device: plane
    — the parser must say 'no device data' (None), not crash, so bench
    callers can fall back to wall-clock."""
    import jax

    from tony_tpu.profiler import device_busy_ms, op_totals_ms, xplane

    logdir = str(tmp_path / "trace")
    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    os.makedirs(logdir, exist_ok=True)
    jax.profiler.start_trace(logdir)
    f(x).block_until_ready()
    jax.profiler.stop_trace()

    files = xplane.xplane_files(logdir)
    assert files, "trace wrote no xplane dump"
    space = xplane.load_xspace(files[-1])
    if space is None:  # proto stubs unavailable in this env: degraded mode
        assert op_totals_ms(logdir) is None
        assert device_busy_ms(logdir) is None
        return
    assert [p.name for p in space.planes]  # parsed something real
    # CPU backend -> no TPU device plane -> None (graceful degradation)
    if not xplane.device_planes(space):
        assert device_busy_ms(logdir) is None


def test_trace_device_ms_cpu_returns_none_or_positive():
    import jax

    from tony_tpu.profiler import trace_device_ms

    f = jax.jit(lambda a: (a @ a).sum())
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    out = trace_device_ms(f, (x,), steps=2)
    assert out is None or out > 0


def test_hbm_estimate_bytes():
    import jax

    from tony_tpu.profiler import hbm_estimate_bytes

    f = jax.jit(lambda a: a @ a)
    x = jnp.ones((64, 64), jnp.float32)
    est = hbm_estimate_bytes(f, x)
    # args (16 KB) + out (16 KB); CPU backends may report nothing (0)
    assert est == 0 or est >= 2 * 64 * 64 * 4


def test_hbm_estimate_bytes_bad_input_is_zero():
    from tony_tpu.profiler import hbm_estimate_bytes

    assert hbm_estimate_bytes(object()) == 0


def _synthetic_two_plane_xspace(tmp_path):
    """Build an XSpace with TWO device planes (a 2-chip trace): plane 0
    runs ops totalling 5 ms, plane 1 totalling 4 ms. Skips when the
    tensorflow proto stubs are unavailable (the parser degrades to None
    there anyway)."""
    os.environ.setdefault("PROTOCOL_BUFFERS_PYTHON_IMPLEMENTATION",
                          "python")
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    per_plane_ms = [(3.0, 2.0), (4.0,)]
    for i, durs in enumerate(per_plane_ms):
        plane = space.planes.add()
        plane.name = f"/device:TPU:{i}"
        meta = plane.event_metadata[1]
        meta.id = 1
        meta.name = f"%fusion.{i} = f32[8]{{0}} fusion(%p0)"
        line = plane.lines.add()
        line.name = "XLA Ops"
        for ms in durs:
            ev = line.events.add()
            ev.metadata_id = 1
            ev.duration_ps = int(ms * 1e9)
    # a host plane rides along and must be ignored
    host = space.planes.add()
    host.name = "/host:CPU"
    logdir = tmp_path / "twoplane"
    os.makedirs(logdir)
    with open(logdir / "x.xplane.pb", "wb") as f:
        f.write(space.SerializeToString())
    return str(logdir)


def test_device_busy_ms_multi_plane_reports_busiest_not_sum(tmp_path):
    """The ADVICE-r5 satellite pin: device_busy_ms on a 2-plane trace
    reports the BUSIEST plane (critical-path chip, comparable to wall
    clock) — the old cross-plane sum over-reported by n_devices."""
    from tony_tpu.profiler import (device_busy_ms, op_totals_ms,
                                   per_plane_op_totals_ms)

    logdir = _synthetic_two_plane_xspace(tmp_path)
    per_plane = per_plane_op_totals_ms(logdir)
    assert set(per_plane) == {"/device:TPU:0", "/device:TPU:1"}
    assert sum(per_plane["/device:TPU:0"].values()) == 5.0
    assert sum(per_plane["/device:TPU:1"].values()) == 4.0
    # busiest plane, NOT the 9 ms cross-chip sum
    assert device_busy_ms(logdir) == 5.0
    # the per-op breakdown view still sums across chips (documented)
    assert sum(op_totals_ms(logdir).values()) == 9.0


# ------------------------------------------------------- ServeProfiler


class _FakeJaxProfiler:
    def __init__(self):
        self.started = []
        self.stopped = 0

    class ProfileOptions:
        python_tracer_level = 1

    def start_trace(self, logdir, profiler_options=None):
        # a serving capture runs without the Python call tracer
        assert profiler_options.python_tracer_level == 0
        self.started.append(logdir)

    def stop_trace(self):
        self.stopped += 1


def test_serve_profiler_request_poll_protocol(tmp_path, monkeypatch):
    """The on-demand serving capture state machine: request(N) arms,
    the first working poll starts the trace, each later poll burns a
    step, the Nth stops it; double-arm is refused while busy."""
    import jax

    from tony_tpu.profiler import ServeProfiler

    fake = _FakeJaxProfiler()
    monkeypatch.setattr(jax, "profiler", fake)
    prof = ServeProfiler(default_logdir=str(tmp_path))
    assert not prof.busy
    prof.poll()  # idle poll: near-free no-op
    assert fake.started == []

    logdir = prof.request(2)
    assert prof.busy and logdir.startswith(str(tmp_path))
    with pytest.raises(RuntimeError, match="already"):
        prof.request(1)  # one global jax profiler session
    prof.poll()  # starts the trace
    assert fake.started == [logdir] and fake.stopped == 0
    prof.poll()  # burns step 1 of 2
    assert fake.stopped == 0 and prof.status()["steps_left"] == 1
    prof.poll()  # burns step 2: capture finishes
    assert fake.stopped == 1
    assert prof.captures == 1 and prof.last_logdir == logdir
    assert not prof.busy
    prof.poll()  # back to the near-free idle path
    assert fake.stopped == 1

    # re-armable after a finished capture; close() finalizes a capture
    # left mid-flight (gateway shutdown)
    prof.request(5)
    prof.poll()   # started
    prof.close()
    assert fake.stopped == 2 and prof.captures == 2
    assert not prof.busy
    with pytest.raises(RuntimeError, match="closed"):
        prof.request(1)  # close() is terminal: the gateway drained


def test_serve_profiler_start_failure_degrades(tmp_path, monkeypatch):
    """A broken profiler must not take the serving loop with it: the
    capture is abandoned with last_error set, polls return to idle."""
    import jax

    from tony_tpu.profiler import ServeProfiler

    class _Broken(_FakeJaxProfiler):
        def start_trace(self, logdir, profiler_options=None):
            raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "profiler", _Broken())
    prof = ServeProfiler(default_logdir=str(tmp_path))
    prof.request(3)
    prof.poll()
    assert not prof.busy
    assert "no backend" in prof.last_error
    assert prof.captures == 0
    with pytest.raises(ValueError):
        prof.request(0)
