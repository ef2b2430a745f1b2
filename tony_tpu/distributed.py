"""User-facing distributed init: the TPU-native replacement for reading
TF_CONFIG / RANK / DMLC_* by hand.

A training script launched by tony-tpu calls::

    import tony_tpu.distributed as dist
    dist.initialize()          # jax.distributed from injected env
    mesh = dist.default_mesh() # all devices, named ("data",)

which wires jax.distributed.initialize(coordinator_address, num_processes,
process_id) from the env the JaxRuntime injected (SURVEY.md section 2.5:
the launcher's whole job is computing this spec and exporting the env).
Safe on a single process with no env: becomes a no-op.
"""

from __future__ import annotations

import json
import logging
import os

from tony_tpu import constants as C

log = logging.getLogger(__name__)


def env_spec() -> dict | None:
    """The injected rendezvous env, or None outside a tony-tpu task."""
    addr = os.environ.get(C.COORDINATOR_ADDRESS)
    if not addr:
        return None
    return {
        "coordinator_address": addr,
        "process_id": int(os.environ.get(C.PROCESS_ID, "0")),
        "num_processes": int(os.environ.get(C.NUM_PROCESSES, "1")),
        "cluster_spec": json.loads(os.environ.get(C.CLUSTER_SPEC, "{}")),
    }


def initialize(timeout_s: int | None = None) -> dict | None:
    """Call jax.distributed.initialize from injected env. No-op (returns
    None) when running outside a gang or with a single process."""
    from tony_tpu.profiler import maybe_start_server
    from tony_tpu.utils import compilecache

    # before any compile: arm XLA's persistent cache (one directory for
    # every entry point — utils/compilecache.py) so retries/resumes and
    # other gang members on this host reuse compiled executables
    compilecache.enable()

    spec = env_spec()
    if spec is None or spec["num_processes"] <= 1:
        log.info("single-process run; skipping jax.distributed.initialize")
        maybe_start_server()  # the profiler port applies at any gang size
        return spec
    import jax

    kwargs = {}
    if timeout_s is not None:
        kwargs["initialization_timeout"] = timeout_s
    jax.distributed.initialize(
        coordinator_address=spec["coordinator_address"],
        num_processes=spec["num_processes"],
        process_id=spec["process_id"],
        **kwargs,
    )
    log.info(
        "jax.distributed initialized: process %d/%d via %s",
        spec["process_id"], spec["num_processes"], spec["coordinator_address"],
    )
    maybe_start_server()  # TONY_PROFILER_PORT-gated; no-op otherwise
    return spec


def default_mesh(axis_name: str = "data"):
    """All addressable devices as a 1-D data-parallel mesh."""
    import jax
    from jax.sharding import Mesh

    return Mesh(jax.devices(), (axis_name,))


def task_identity() -> tuple[str, int]:
    """(role, index) of this task, or ("", 0) outside a job."""
    return os.environ.get(C.JOB_NAME, ""), int(os.environ.get(C.TASK_INDEX, "0"))


def is_chief() -> bool:
    return os.environ.get(C.IS_CHIEF, "false") == "true"
