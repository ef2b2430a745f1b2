"""``python -m tony_tpu.cli.replica`` — one replica agent on this host.

The remote TaskExecutor of the serving story: boots ONE
``serve.Server`` (same engine knobs as the gateway CLI) behind the
agent HTTP shim (``serve/agent.py``) and waits. The gateway launches
this on provisioned hosts (``--remote-replica`` / the provisioner
backend) or attaches to already-running ones (``--agents``), then
drives it over POST /v1/submit + resumable GET /v1/stream.

    python -m tony_tpu.cli.replica --demo-model --port 8101

SIGTERM/SIGINT deregisters by DRAINING: new submits 503, every
in-flight and pending request finishes, then exit 0 — the gateway's
lease sees ``draining`` on /healthz instead of a vanished host. A
second signal force-exits.

``--port-file`` writes "host port" once the socket is bound — how a
launcher (gateway ``--remote-replica``, tools/serve_smoke.sh) learns
an ephemeral port without parsing stdout.

``--replica-index`` addresses ``TONY_SERVE_FAULTS`` engine faults at
this agent (chaos rounds arm replica N's ENGINE here while the
gateway arms replica N's TRANSPORT at its stub — one env var, both
failure planes).
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tony-tpu replica",
        description="one serving replica agent (engine + HTTP shim)")
    src = p.add_mutually_exclusive_group(required=True)
    src.add_argument("--model", help="local checkpoint directory (HF format)")
    src.add_argument("--demo-model", action="store_true",
                     help="serve a tiny random decoder (no checkpoint) "
                          "— for smoke tests")
    p.add_argument("--serve-batch", type=int, default=4,
                   help="cache slots")
    p.add_argument("--chunk-steps", type=int, default=1)
    p.add_argument("--prefill-chunk-tokens", type=int, default=0)
    p.add_argument("--prefix-cache-mb", type=float, default=64.0)
    p.add_argument("--kv-host-mb", type=float, default=0.0)
    p.add_argument("--speculate-k", type=int, default=0)
    p.add_argument("--kv-page-size", type=int, default=0)
    p.add_argument("--kv-pages", type=int, default=0)
    p.add_argument("--no-paged-kv", action="store_true")
    p.add_argument("--mesh", default="",
                   help="sharded replica: devices for THIS agent's "
                        "engine (count or 'tensor=N,expert=M'; see "
                        "cli.gateway --mesh)")
    p.add_argument("--shard-rules", default="serve")
    p.add_argument("--max-pending", type=int, default=1024)
    p.add_argument("--eos-id", type=int, default=-1)
    p.add_argument("--dtype", choices=("fp32", "bf16"), default="fp32")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8101,
                   help="0 picks an ephemeral port (see --port-file)")
    p.add_argument("--port-file", default="",
                   help="write 'host port' here once bound — how a "
                        "launcher learns an ephemeral port")
    p.add_argument("--replica-index", type=int, default=0,
                   help="fleet index for TONY_SERVE_FAULTS engine-"
                        "fault addressing")
    p.add_argument("--host-share", type=int, default=1,
                   help="how many agents share THIS host's HBM "
                        "(auto-sized KV page pools divide by it; a "
                        "gateway launching N localhost agents passes "
                        "its fleet ceiling so the pools cannot "
                        "oversubscribe the device). 1 = alone on the "
                        "host (the provisioned-slice default)")
    p.add_argument("--agent-id", default="",
                   help="stable id reported on /healthz (default: "
                        "a generated one)")
    p.add_argument("--profile-dir", default="",
                   help="where POST /v1/profile (the gateway's "
                        "/debug/profile fan-out) drops THIS host's "
                        "xplane captures (default: "
                        "$TONY_PROFILE_DIR or ./profiles)")
    p.add_argument("--drain-timeout", type=float, default=120.0,
                   help="max seconds to finish in-flight work on "
                        "SIGTERM")
    p.add_argument("--park-ttl", type=float, default=60.0,
                   help="seconds a parked session (orphaned snapshot "
                        "or finished-but-undelivered result) stays "
                        "adoptable before it is reaped")
    p.add_argument("--gateway-grace", type=float, default=0.0,
                   help="seconds of gateway silence before in-flight "
                        "slots freeze into parked snapshots (0 "
                        "disables the watchdog; in-flight work runs "
                        "to completion and parks as results)")
    p.add_argument("--compile-cache", default=None,
                   help="persistent XLA compile-cache dir; default: "
                        "JAX_COMPILATION_CACHE_DIR when set, else "
                        "<checkout>/.jax_compile_cache ('' disables)")
    return p


def build_server(args):
    """The engine, configured exactly like a gateway boot replica
    (cli/gateway.server_factory) — remote must not mean different."""
    from tony_tpu.cli.gateway import demo_model, server_factory

    if args.demo_model:
        model, params = demo_model()
        eos = [args.eos_id] if args.eos_id >= 0 else []
    else:
        from tony_tpu.cli.generate import load_model
        from tony_tpu.models.generate import normalize_eos_ids

        model, wrapped, config = load_model(args.model)
        params = wrapped["params"]
        if args.dtype == "bf16":
            import jax
            import jax.numpy as jnp

            params = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
        eos = normalize_eos_ids(args.eos_id) or \
            normalize_eos_ids(getattr(config, "eos_token_id", None))
    # this process IS one replica, but auto-sized KV pools must still
    # divide the host's HBM by every agent sharing it — the factory's
    # fleet-ceiling sizing keyed off args.replicas does exactly that
    args.replicas = max(1, args.host_share)
    return server_factory(args, model, params, eos)(args.replica_index)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    logging.basicConfig(level=logging.INFO)
    if args.compile_cache != "":
        from tony_tpu.utils import compilecache

        compilecache.enable(args.compile_cache)

    from tony_tpu.serve.agent import AgentHTTP, ReplicaAgent

    server = build_server(args)
    if server.fault_plan is not None:
        logging.getLogger(__name__).warning(
            "engine fault injection ARMED on this agent (replica %d) "
            "via TONY_SERVE_FAULTS", args.replica_index)
    agent = ReplicaAgent(server, agent_id=args.agent_id or None,
                         profile_dir=args.profile_dir or None,
                         park_ttl_s=args.park_ttl,
                         gateway_grace_s=args.gateway_grace)
    http = AgentHTTP(agent, host=args.host, port=args.port).start()
    if args.port_file:
        tmp = args.port_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{http.host} {http.port}\n")
        os.replace(tmp, args.port_file)  # atomic: launchers poll it
    print(f"tony-tpu replica agent {agent.agent_id} at "
          f"http://{http.host}:{http.port}", flush=True)

    signals_seen = []

    def _on_signal(signum, frame):
        # count SIGNALS, not agent.draining: a gateway-initiated
        # /v1/drain followed by one polite SIGTERM (the scale-down /
        # close() sequence) must exit 0, not take the force path
        signals_seen.append(signum)
        if len(signals_seen) > 1:  # second signal: force exit
            os._exit(1)
        print(f"signal {signum}: draining agent (new submits 503, "
              f"finishing in-flight)...", file=sys.stderr, flush=True)
        # drain on a helper thread: the handler must return promptly
        # (idempotent — a drain already running just finishes)
        import threading

        threading.Thread(target=agent.drain,
                         args=(args.drain_timeout,),
                         daemon=True).start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    agent.drained.wait()
    http.stop()
    print("agent drained clean", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
