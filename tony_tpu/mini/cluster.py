"""In-process test cluster harness — tony-mini MiniCluster equivalent.

Reference: tony-mini MiniCluster.java:24-87 boots MiniYARNCluster +
MiniDFSCluster in-process so E2E tests submit real jobs without a cluster.
Here there is no RM/NM to fake: the local launcher already runs agents as
subprocesses, so the harness provides (a) an isolated staging/history root,
(b) fast control-plane timings, (c) a ``submit`` helper mirroring
TestTonyE2E's client usage, and (d) CPU-forcing env for jax payloads.
"""

from __future__ import annotations

import os
import shutil
import tempfile

from tony_tpu.client import TonyClient
from tony_tpu.config import TonyConf


class MiniTonyCluster:
    def __init__(self, fast_ms: int = 100):
        self.fast_ms = fast_ms
        self.root: str = ""

    def __enter__(self) -> "MiniTonyCluster":
        self.root = tempfile.mkdtemp(prefix="minitony_")
        # the local harness is CPU-only by contract: payload scripts
        # inherit this env, so none of them opens a chip the caller
        # (a bench, a test run on a TPU VM) may be holding
        os.environ["JAX_PLATFORMS"] = "cpu"
        return self

    def __exit__(self, *exc) -> None:
        shutil.rmtree(self.root, ignore_errors=True)

    def base_conf(self) -> TonyConf:
        conf = TonyConf()
        conf.set("tony.staging-dir", os.path.join(self.root, "staging"))
        conf.set("tony.history.location", os.path.join(self.root, "history"))
        conf.set("tony.task.heartbeat-interval-ms", self.fast_ms)
        conf.set("tony.coordinator.monitor-interval-ms", self.fast_ms)
        conf.set("tony.client.poll-interval-ms", self.fast_ms)
        conf.set("tony.coordinator.registration-timeout-ms", 60_000)
        return conf

    def adopt(self, conf: TonyConf) -> TonyConf:
        """Overlay this cluster's staging/history/timing keys onto an
        externally-built conf (the one merge both `tony-tpu local` and the
        test harness use)."""
        base = self.base_conf()
        for key in ("tony.staging-dir", "tony.history.location",
                    "tony.task.heartbeat-interval-ms",
                    "tony.coordinator.monitor-interval-ms",
                    "tony.client.poll-interval-ms",
                    "tony.coordinator.registration-timeout-ms"):
            conf.set(key, base.get(key))
        return conf

    def make_client(self, conf: TonyConf) -> TonyClient:
        return TonyClient(conf)

    def submit(self, conf: TonyConf) -> TonyClient:
        """Run a job to completion; returns the client (check
        ``client.final_status``)."""
        client = self.make_client(conf)
        client.run()
        return client


def script_conf(cluster: MiniTonyCluster, script: str, roles: dict[str, int],
                framework: str = "jax", **extra) -> TonyConf:
    """Conf for a payload-script job (TestTonyE2E helper shape)."""
    conf = cluster.base_conf()
    conf.set("tony.application.executes", script)
    conf.set("tony.application.framework", framework)
    for role, n in roles.items():
        conf.set(f"tony.{role}.instances", n)
    for k, v in extra.items():
        conf.set(k, v)
    return conf
