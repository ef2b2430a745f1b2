"""``tony-tpu gateway`` — the HTTP serving front door.

Boots N data-parallel ``serve.Server`` replicas (one scheduler thread
each, weights shared, KV caches private) behind ``tony_tpu.gateway``:
bounded admission with per-request deadlines, least-outstanding-tokens
routing, graceful drain on SIGTERM, per-request metrics on ``/stats``
(and in the portal via ``--history``).

    python -m tony_tpu.cli.gateway --model ./my-llama \
        --replicas 2 --serve-batch 4 --port 8000

    curl -s localhost:8000/v1/generate -d \
        '{"prompt": "Once upon a time", "max_new_tokens": 32}'

``--demo-model`` serves a tiny randomly initialized decoder instead of
a checkpoint — token_ids-only, but boots in seconds on CPU: the smoke
target (``make serve-smoke``) and quick integration checks use it.

Shutdown: SIGTERM/SIGINT stops admission (``/readyz`` -> 503 so a load
balancer pulls the replica), finishes every queued + in-flight request,
then exits 0. A second signal force-exits.

Fault tolerance (the TonY supervision story, serving flavor): replica
threads heartbeat; a watchdog fails a replica whose beats stall past
``--stall-timeout``, its requests fail over token-exactly to healthy
replicas (up to ``--max-attempts`` engine runs each), and the failed
replica re-earns admission through a circuit breaker
(``--breaker-base``/``--breaker-max`` backoff, ``--quarantine-after``
strikes). ``TONY_SERVE_FAULTS`` arms deterministic fault injection for
chaos testing (``make chaos-smoke``; see ``serve/faults.py``).

Goodput + alerts (ISSUE-10; docs/OBSERVABILITY.md): every dispatch is
priced by an analytic cost model (bytes/FLOPs, HBM-BW%/MFU with
``--hbm-gbps`` or a known chip), the wall clock decomposes into a
goodput ledger (``/stats engine.goodput``, ``GET /debug/goodput``
names the largest waste bucket), and a rule engine fires deduplicated
alerts (queue aging, KV-page pressure, TTFT-SLO burn, breaker flap,
goodput collapse) into ``/stats alerts``, ``tony_alerts_*`` and
history ``metrics/alerts.jsonl`` (``--alert-*`` knobs, ``--no-alerts``
off switch).

Elastic autoscaling + admission tiers (ISSUE-9; docs/SERVING.md):
``--autoscale-max N`` arms the control loop — the fleet grows from
``--replicas`` up to N under queue/SLO pressure (new replicas join
via circuit-breaker probe admission) and drains back to
``--autoscale-min`` when idle (zero-loss). Requests may carry
``priority`` (weighted-fair-queued tiers, ``--tier-weights``) and
``tenant`` (token-rate quotas, ``--tenant-quota`` -> 429 +
Retry-After on breach).
"""

from __future__ import annotations

import argparse
import logging
import os
import signal
import sys
import threading


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tony-tpu gateway",
        description="HTTP serving front door over N continuous-batching "
                    "replicas")
    src = p.add_mutually_exclusive_group()
    src.add_argument("--model", help="local checkpoint directory (HF format)")
    src.add_argument("--demo-model", action="store_true",
                     help="serve a tiny random decoder (no checkpoint, "
                          "token_ids requests only) — for smoke tests")
    p.add_argument("--remote-replica", action="store_true",
                   help="serve ON replica agents instead of in-process "
                        "threads: launch one `python -m "
                        "tony_tpu.cli.replica` subprocess per replica "
                        "(localhost; provisioned hosts run the same CLI "
                        "there) and drive each through a RemoteServer "
                        "stub — lease heartbeats, epoch fencing, "
                        "resumable token streams, token-exact failover "
                        "on host death (docs/SERVING.md)")
    p.add_argument("--agents", default="",
                   help="comma-separated host:port of ALREADY RUNNING "
                        "replica agents to attach to (implies remote "
                        "mode; the fleet is this list and the gateway "
                        "process loads no model weights at all)")
    p.add_argument("--agent-heartbeat", type=float, default=1.0,
                   help="gateway->agent heartbeat interval in seconds; "
                        "the lease horizon is interval x max(3, "
                        "--agent-lease-misses) — no successful "
                        "heartbeat for that long fails the replica "
                        "over (token-exact)")
    p.add_argument("--agent-lease-misses", type=int, default=5,
                   help="missed heartbeats before an agent's lease "
                        "expires (see --agent-heartbeat)")
    p.add_argument("--agent-channel", choices=("mux", "per-ticket"),
                   default="mux",
                   help="gateway->agent streaming transport: 'mux' is "
                        "ONE long-lived connection per replica "
                        "carrying every ticket stream as tagged "
                        "frames (reconnect re-establishes all of them "
                        "at their offsets in one round trip); "
                        "'per-ticket' keeps the one-connection-per-"
                        "request readers as the A/B control")
    p.add_argument("--replicas", type=int, default=1,
                   help="data-parallel serve.Server replicas (each with "
                        "its own KV cache and scheduler thread)")
    p.add_argument("--serve-batch", type=int, default=4,
                   help="cache slots per replica")
    p.add_argument("--chunk-steps", type=int, default=1,
                   help="decode micro-steps fused per dispatch; 1 = "
                        "lowest per-token streaming latency, larger = "
                        "higher throughput")
    p.add_argument("--prefill-chunk-tokens", type=int, default=0,
                   help="chunked prefill: max prompt tokens one "
                        "admission dispatch may consume (quantized to "
                        "the prefill bucket grid); long prompts "
                        "prefill in chunks interleaved between decode "
                        "rounds, capping co-tenant TPOT/TTFT "
                        "starvation. 0 = monolithic (the default)")
    p.add_argument("--roles", default="",
                   help="disaggregated prefill/decode: "
                        "'prefill=N,decode=M' splits the fleet into a "
                        "prefill pool (admission + chunked prefill "
                        "only; finished prompts hand off as page "
                        "lists) and a decode pool (receives handoffs, "
                        "decodes). Overrides --replicas to N+M; needs "
                        "the paged KV cache; token-exact vs a "
                        "generalist fleet (docs/SERVING.md)")
    p.add_argument("--no-prefix-affinity", action="store_true",
                   help="disable prefix-affinity routing (requests "
                        "route to the replica whose radix tree holds "
                        "their longest cached prefix; this flag is "
                        "the A/B control — routing degrades to "
                        "least-outstanding-tokens)")
    p.add_argument("--kv-host-mb", type=float, default=0.0,
                   help="host-RAM KV page tier byte budget per "
                        "replica: evicted prefix-store pages spill "
                        "device->host and page back in on a prefix "
                        "hit (bitwise round trip), so prefix reuse "
                        "stops being bounded by HBM. 0 disables; "
                        "needs paged KV + a prefix store; traffic "
                        "shows on /stats under engine.kv_host")
    p.add_argument("--prefix-cache-mb", type=float, default=64.0,
                   help="per-replica byte budget for the prefix "
                        "KV-cache store (radix reuse of shared prompt "
                        "prefixes: exact repeats skip prefill, shared "
                        "system prompts prefill only their suffix). "
                        "0 disables; hit rates show on /stats under "
                        "engine.prefix")
    p.add_argument("--speculate-k", type=int, default=0,
                   help="speculative decoding: max draft tokens per "
                        "slot per verify dispatch (prompt-lookup "
                        "n-gram drafting, batched multi-token "
                        "verification; greedy outputs unchanged, "
                        "sampled requests unaffected). 0 disables; "
                        "acceptance shows on /stats under engine.spec")
    p.add_argument("--kv-page-size", type=int, default=0,
                   help="tokens per KV-cache page (the block-paged "
                        "cache: residency bounded by actual tokens, "
                        "prefix reuse by copy-on-write page sharing). "
                        "0 auto-sizes from max_seq_len; utilization "
                        "shows on /stats under engine.kv_pages")
    p.add_argument("--kv-pages", type=int, default=0,
                   help="KV page-pool size per replica; 0 auto-sizes "
                        "(the unpaged-equivalent footprint, grown "
                        "into the HBM the device reports free — same "
                        "resolution style as --prefix-cache-mb)")
    p.add_argument("--no-paged-kv", action="store_true",
                   help="serve fixed-shape per-slot cache rows instead "
                        "of the paged pool (A/B escape hatch; "
                        "sliding-window models downgrade automatically)")
    p.add_argument("--warm-views", action="store_true",
                   help="compile the decode program for every page-view "
                        "bucket at start-up instead of when a live row "
                        "first grows into one (no compile stall under "
                        "traffic, at the cost of a longer boot); with "
                        "--prefill-chunk-tokens also every program of a "
                        "chunked prefill")
    p.add_argument("--no-shared-pool", action="store_true",
                   help="give each in-process replica its own private "
                        "KV page pool instead of one gateway-owned "
                        "shared pool (the shared pool makes "
                        "prefill->decode handoffs and live session "
                        "migration zero-copy owner swaps, and pools "
                        "the fleet's free-page headroom)")
    p.add_argument("--mesh", default="",
                   help="sharded replicas (ISSUE-14): devices per "
                        "replica as a bare count (tensor-parallel, "
                        "'--mesh 4') or an axis spec "
                        "('tensor=4,expert=2'). Params shard on "
                        "output dims, KV page pools on the kv-head "
                        "axis; streams are byte-identical to a "
                        "single-chip replica on the CPU backend — on "
                        "TPU chips the logits agree to rounding and "
                        "the streams can diverge (PERF.md, PR 24). "
                        "'' = single-chip (the default); topology "
                        "shows on /stats under engine.mesh")
    p.add_argument("--shard-rules", default="serve",
                   help="parallel.sharding rule preset for --mesh "
                        "(default 'serve' — the only preset that "
                        "shards no contraction dim)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=8000,
                   help="0 picks an ephemeral port")
    p.add_argument("--edge", choices=("event", "threaded"),
                   default="event",
                   help="HTTP front end: 'event' (default) is the "
                        "selector edge — one loop thread plus a small "
                        "fixed worker pool holds tens of thousands of "
                        "concurrent NDJSON streams; 'threaded' is the "
                        "thread-per-connection stdlib server, kept as "
                        "the A/B control")
    p.add_argument("--edge-max-connections", type=int, default=16384,
                   help="event edge connection breaker: past this "
                        "many open sockets new connections shed 503 "
                        "with Retry-After instead of degrading "
                        "everyone (threaded edge ignores this)")
    p.add_argument("--edge-workers", type=int, default=4,
                   help="event edge worker threads for blocking "
                        "gateway calls (submit, snapshot); the edge "
                        "itself stays on one loop thread")
    p.add_argument("--edge-write-buffer-kb", type=int, default=256,
                   help="event edge per-connection write buffer bound "
                        "in KiB; a client that cannot keep up beyond "
                        "it gets --edge-drain-timeout to catch up")
    p.add_argument("--edge-drain-timeout", type=float, default=10.0,
                   help="event edge slow-client policy: seconds a "
                        "full write buffer may take to drain before "
                        "the stream is aborted (counted, never pins "
                        "a worker thread)")
    p.add_argument("--edge-io-timeout", type=float, default=30.0,
                   help="event edge bound on reading one request "
                        "(head+body) once its first byte arrives — "
                        "trickled uploads get 408; IDLE keep-alive "
                        "connections are exempt and cost nothing")
    p.add_argument("--max-queue", type=int, default=128,
                   help="admission queue bound; past it requests shed "
                        "with 429")
    p.add_argument("--max-pending", type=int, default=1024,
                   help="per-replica engine queue bound (serve.QueueFull)")
    p.add_argument("--default-ttl", type=float, default=None,
                   help="default per-request deadline in seconds "
                        "(requests may override with ttl_s); expired "
                        "requests shed with 504 before taking a slot")
    p.add_argument("--eos-id", type=int, default=-1,
                   help="stop token (default: model config's "
                        "eos_token_id)")
    p.add_argument("--dtype", choices=("fp32", "bf16"), default="fp32",
                   help="parameter storage dtype (bf16 halves decode "
                        "HBM traffic — the serving default on TPU)")
    p.add_argument("--history", default="",
                   help="job-history root: record the gateway as a "
                        "portal-browsable job with per-request metrics "
                        "and Chrome-trace rows (metrics/traces.jsonl)")
    p.add_argument("--profile-dir", default="",
                   help="where POST /debug/profile drops its xplane "
                        "captures (default: <history job dir>/profiles "
                        "with --history, else ./profiles)")
    p.add_argument("--journal", action="store_true",
                   help="arm the durable ticket journal (ISSUE-20): a "
                        "write-ahead NDJSON log of every admit/route/"
                        "emit-offset/terminal under the history job "
                        "dir, compacted away on clean drain — the "
                        "record --recover replays after a crash. "
                        "Needs --history for a place to land")
    p.add_argument("--journal-fsync", default="batch",
                   choices=("always", "batch", "off"),
                   help="journal durability: 'always' fsyncs every "
                        "append, 'batch' (default) fsyncs admits and "
                        "terminals while emit offsets ride the page "
                        "cache, 'off' never fsyncs")
    p.add_argument("--recover", action="store_true",
                   help="crash recovery boot: replay the newest "
                        "journal under the --history root and "
                        "re-admit every still-live request — parked "
                        "agent sessions are adopted mid-stream "
                        "(token-exact, zero re-prefill), local ones "
                        "re-run from the prompt; clients resume via "
                        "GET /v1/stream/<id>?offset=. A no-op when "
                        "the previous boot drained clean")
    p.add_argument("--park-ttl", type=float, default=60.0,
                   help="seconds a terminal request stays resumable "
                        "at the gateway (GET /v1/stream/<id>) and a "
                        "launched agent keeps orphaned sessions "
                        "adoptable")
    p.add_argument("--agent-grace", type=float, default=0.0,
                   help="launched agents: seconds of gateway silence "
                        "before their in-flight slots freeze into "
                        "parked snapshots (forwarded as the replica "
                        "CLI's --gateway-grace; 0 = park only "
                        "finished results)")
    p.add_argument("--trace-capacity", type=int, default=256,
                   help="recent request traces kept for "
                        "GET /debug/trace/<request_id>; 0 disables "
                        "request tracing")
    p.add_argument("--drain-timeout", type=float, default=120.0,
                   help="max seconds to wait for in-flight requests on "
                        "shutdown")
    p.add_argument("--max-attempts", type=int, default=3,
                   help="engine runs a request may burn across replica "
                        "failures before it sheds 503 (the TonY task-"
                        "retry budget, per request)")
    p.add_argument("--stall-timeout", type=float, default=30.0,
                   help="seconds without a replica-thread heartbeat "
                        "before the watchdog declares it failed and "
                        "fails its requests over; must comfortably "
                        "exceed one step's worst dispatch time "
                        "(first-compile included)")
    p.add_argument("--breaker-base", type=float, default=0.25,
                   help="circuit breaker: first backoff before a failed "
                        "replica is probed (doubles per consecutive "
                        "failure up to --breaker-max)")
    p.add_argument("--breaker-max", type=float, default=8.0,
                   help="circuit breaker: backoff ceiling in seconds")
    p.add_argument("--quarantine-after", type=int, default=5,
                   help="consecutive failures (probe failures included) "
                        "before a replica is quarantined out of the "
                        "rotation for good")
    p.add_argument("--tier-weights", default="",
                   help="admission tier spec 'name=weight,...' "
                        "(default interactive=8,standard=4,batch=1); "
                        "requests pick a tier via their 'priority' "
                        "field, weights shape WFQ interleaving under "
                        "contention (idle fleets give any tier full "
                        "throughput)")
    p.add_argument("--tenant-quota", type=float, default=0.0,
                   help="per-tenant token-rate quota in tokens/s over "
                        "estimated request cost (prompt + budget); a "
                        "tenant over its rate gets 429 + Retry-After. "
                        "0 disables (the default)")
    p.add_argument("--tenant-burst", type=float, default=0.0,
                   help="per-tenant burst bucket in tokens "
                        "(default 4x --tenant-quota)")
    p.add_argument("--autoscale-max", type=int, default=0,
                   help="arm the elastic autoscaler: grow the fleet "
                        "up to this many replicas under queue/SLO "
                        "pressure (probe-admitted), drain back to "
                        "--autoscale-min when idle. 0 = fixed fleet "
                        "(the default)")
    p.add_argument("--autoscale-min", type=int, default=0,
                   help="fleet floor for scale-down "
                        "(default: --replicas)")
    p.add_argument("--autoscale-interval", type=float, default=1.0,
                   help="autoscaler control-loop tick in seconds")
    p.add_argument("--autoscale-up-queue", type=float, default=4.0,
                   help="queued requests per routable replica that "
                        "count as scale-up pressure")
    p.add_argument("--autoscale-up-wait", type=float, default=1.0,
                   help="oldest queued wait (s) that counts as "
                        "scale-up pressure")
    p.add_argument("--autoscale-ttft-slo", type=float, default=0.0,
                   help="TTFT SLO in seconds: scale-up pressure when "
                        ">10%% of a tick's completions exceed it "
                        "(0 disables the SLO-burn signal)")
    p.add_argument("--autoscale-cooldown-up", type=float, default=5.0,
                   help="lockout after a scale-up (s)")
    p.add_argument("--autoscale-cooldown-down", type=float, default=30.0,
                   help="lockout after a scale-down (s)")
    p.add_argument("--rebalance", action="store_true",
                   help="arm the pressure-driven rebalancer "
                        "(gateway/rebalance.py): watches per-replica "
                        "slot-occupancy skew and live-migrates "
                        "in-flight sessions off the hottest replica, "
                        "token-exact, preferring victims whose prefix "
                        "the cold side already caches")
    p.add_argument("--no-rebalance", action="store_true",
                   help="explicitly disable the rebalancer (the A/B "
                        "control; wins over --rebalance)")
    p.add_argument("--rebalance-interval", type=float, default=1.0,
                   help="rebalancer control-loop tick in seconds")
    p.add_argument("--rebalance-skew", type=float, default=0.5,
                   help="hot-minus-cold occupancy-fraction gap that "
                        "counts as skew (0.5 = 50 points fuller)")
    p.add_argument("--rebalance-stable", type=int, default=2,
                   help="consecutive skewed ticks before a move "
                        "(hysteresis)")
    p.add_argument("--rebalance-cooldown", type=float, default=5.0,
                   help="lockout after a successful move (s); a move "
                        "that found no victim waits twice as long")
    p.add_argument("--autotune", action="store_true",
                   help="arm the ledger-driven adaptive shape "
                   "controller (serve/autotune.py): steers "
                   "chunk-steps / speculate-k / prefill-chunk per "
                   "replica from the goodput ledger, within the "
                   "--autotune-* bounds; decisions go to /stats "
                   "engine.autotune, tony_autotune_* metrics, and "
                   "history metrics/autotune.jsonl")
    p.add_argument("--autotune-interval", type=float, default=1.0,
                   help="seconds between controller ticks")
    p.add_argument("--autotune-chunk-min", type=int, default=1,
                   help="chunk-steps floor the controller may steer to")
    p.add_argument("--autotune-chunk-max", type=int, default=32,
                   help="chunk-steps ceiling (0 pins chunk-steps)")
    p.add_argument("--autotune-spec-max", type=int, default=16,
                   help="speculate-k ceiling (0 pins speculate-k; the "
                   "controller never re-arms speculation from 0)")
    p.add_argument("--autotune-prefill-max", type=int, default=0,
                   help="prefill-chunk-tokens ceiling (0 = leave the "
                   "prefill chunk budget alone)")
    p.add_argument("--autotune-hold", type=int, default=2,
                   help="consecutive same-direction ticks before an "
                   "actuation (hysteresis)")
    p.add_argument("--autotune-cooldown", type=int, default=3,
                   help="ticks after an actuation during which the "
                   "knob is not re-judged")
    p.add_argument("--hbm-gbps", type=float, default=0.0,
                   help="peak HBM bandwidth reference in GB/s for the "
                        "goodput ledger's per-dispatch HBM-BW%% / MFU "
                        "estimates (0 auto-detects from the chip "
                        "table / TONY_HBM_GBPS; unknown chips and CPU "
                        "report bytes with utilization null)")
    p.add_argument("--no-alerts", action="store_true",
                   help="disable the serving alert bus (rule engine "
                        "over queue/KV/SLO/breaker/goodput signals "
                        "feeding /stats alerts, tony_alerts_* and "
                        "history alerts.jsonl) — the A/B escape hatch")
    p.add_argument("--alert-interval", type=float, default=1.0,
                   help="alert rule evaluation cadence in seconds")
    p.add_argument("--alert-queue-wait", type=float, default=5.0,
                   help="queue_aging alert: oldest queued wait (s) "
                        "that counts as an aging queue")
    p.add_argument("--alert-kv-free-frac", type=float, default=0.15,
                   help="kv_pages_pressure alert: free-after-"
                        "reservation fraction of the page pool under "
                        "which live load counts as pressure")
    p.add_argument("--alert-host-thrash-bytes", type=float,
                   default=float(1 << 20),
                   help="kv_host_thrash alert: host-tier page-in "
                        "bytes per evaluation tick that, together "
                        "with kv_pages_pressure, count as "
                        "spill/restore churn")
    p.add_argument("--alert-ttft-slo", type=float, default=0.0,
                   help="ttft_slo_burn alert: TTFT SLO in seconds "
                        "(>10%% of a tick's completions over it "
                        "fires; 0 disables the rule)")
    p.add_argument("--alert-shed-storm", type=int, default=50,
                   help="shed_storm alert: capacity sheds "
                        "(429/503/504, quota excluded) within the "
                        "storm window that count as a storm")
    p.add_argument("--alert-shed-window", type=float, default=10.0,
                   help="shed_storm alert: rate window in seconds")
    p.add_argument("--no-alert-bundles", action="store_true",
                   help="disable the flight recorder: by default a "
                        "FIRING alert dumps one self-contained debug "
                        "bundle (active alerts, recent traces incl. "
                        "remote spans, per-replica dispatch/goodput/"
                        "transport blocks, scale signals) into "
                        "<history job dir>/bundles/ — needs --history "
                        "for a place to land; GET /debug/bundle "
                        "serves the same document on demand either "
                        "way")
    p.add_argument("--compile-cache", default=None,
                   help="persistent XLA compile-cache dir; default: "
                        "JAX_COMPILATION_CACHE_DIR when set, else "
                        "<checkout>/.jax_compile_cache ('' disables)")
    return p


def demo_model():
    """A tiny random decoder: boots in seconds on CPU, exercises the
    whole serving stack (prefill buckets, per-slot decode, EOS evict)."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import Transformer, TransformerConfig

    # 4 heads so a --mesh 4 tensor axis divides the kv-head dim (the
    # shard-smoke round serves this model 4-way sharded); outputs are
    # only ever compared control-vs-treatment within one boot
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def parse_mesh(spec: str):
    """``--mesh`` -> a ``jax.sharding.Mesh`` over the FIRST N local
    devices, or None for single-chip. A bare count means pure tensor
    parallelism (``--mesh 4`` == ``tensor=4``); an axis spec names
    sizes per ``parallel.mesh`` axis (``tensor=4,expert=2`` -> 8
    devices/replica). Built once per process — every replica shares
    the mesh (its own params/pools, the same chips), exactly like
    the single-chip fleet shares the host."""
    s = spec.strip()
    if not s:
        return None
    import jax

    from tony_tpu.parallel.mesh import ALL_AXES, MeshSpec, make_mesh

    sizes = {}
    if s.isdigit():
        sizes["tensor"] = int(s)
    else:
        for part in s.split(","):
            name, sep, val = part.strip().partition("=")
            if not sep or name not in ALL_AXES:
                raise SystemExit(
                    f"--mesh expects a device count or 'axis=N,...' "
                    f"over {ALL_AXES}, got {spec!r}")
            try:
                sizes[name] = int(val)
            except ValueError:
                raise SystemExit(
                    f"--mesh size {val!r} is not an integer") from None
    n = 1
    for v in sizes.values():
        if v < 1:
            raise SystemExit(f"--mesh sizes must be >= 1, got {spec!r}")
        n *= v
    devices = jax.devices()
    if n > len(devices):
        raise SystemExit(
            f"--mesh {spec!r} needs {n} devices, "
            f"{len(devices)} visible")
    kwargs = {a: 1 for a in ALL_AXES}
    kwargs.update(sizes)
    return make_mesh(MeshSpec(**kwargs), devices=devices[:n])


def server_factory(args, model, params, eos):
    """One replica engine from parsed args — shared by boot-time
    construction AND the autoscaler's ThreadBackend, so a dynamically
    added replica is configured identically to a boot one (weights
    shared; its own KV cache/prefix store; TONY_SERVE_FAULTS applies
    by its fleet index, so chaos rounds can arm dynamic replicas
    too)."""
    from tony_tpu.cli.generate import (resolve_paged_kv,
                                       resolve_prefix_cache_mb)
    from tony_tpu.serve import FaultPlan, Server

    prefix_mb = resolve_prefix_cache_mb(args, model)
    # size the per-replica KV pool for the fleet CEILING: a pool sized
    # for --replicas would oversubscribe HBM the moment the scaler
    # grows past it
    ceiling = max(1, args.replicas,
                  getattr(args, "autoscale_max", 0) or 0)
    paged_kw = resolve_paged_kv(args, model, args.serve_batch,
                                n_replicas=ceiling)
    # one mesh per process, shared by every replica this factory mints
    # (including autoscaler-grown ones): each gets its own sharded
    # params/pools over the same chips
    mesh = parse_mesh(getattr(args, "mesh", ""))

    # the host tier spills EVICTED prefix-store entries: with the
    # store resolved off there is nothing to spill — downgrade loudly
    # instead of letting Server() refuse the whole boot
    kv_host_mb = getattr(args, "kv_host_mb", 0.0)
    if kv_host_mb > 0 and prefix_mb <= 0:
        logging.getLogger(__name__).warning(
            "--kv-host-mb ignored: the host page tier needs a prefix "
            "store (--prefix-cache-mb > 0)")
        kv_host_mb = 0.0

    # ONE gateway-owned shared PagePool lent to every co-located
    # replica (ISSUE-18): prefill->decode handoffs and live session
    # migration between in-process replicas become zero-copy refcount
    # owner swaps, and the fleet's free-page headroom is pooled (a
    # retiring replica's pages are instantly usable by the survivors).
    # Sized for the fleet CEILING — the same HBM the per-replica pools
    # would have held between them, in one allocation.
    pool = None
    if paged_kw.get("paged") \
            and not getattr(args, "no_shared_pool", False):
        from tony_tpu.serve.slots import PagePool, default_page_size

        cfg = model.cfg
        ps = paged_kw.get("kv_page_size", 0) \
            or default_page_size(cfg)
        ps = max(1, min(int(ps), cfg.max_seq_len))
        per_replica = paged_kw.get("kv_pages", 0) \
            or args.serve_batch * (-(-cfg.max_seq_len // ps))
        pool = PagePool(model, params, int(per_replica) * ceiling, ps,
                        mesh=mesh, shared=True)

    def make(index: int):
        return Server(model, params, batch_size=args.serve_batch,
                      eos_id=eos, chunk_steps=args.chunk_steps,
                      max_pending=args.max_pending,
                      prefix_cache_mb=prefix_mb,
                      speculate_k=args.speculate_k,
                      fault_plan=FaultPlan.from_env(replica=index),
                      hbm_gbps=getattr(args, "hbm_gbps", 0.0),
                      prefill_chunk_tokens=getattr(
                          args, "prefill_chunk_tokens", 0),
                      kv_host_mb=kv_host_mb,
                      mesh=mesh,
                      shard_rules=getattr(args, "shard_rules", "serve"),
                      page_pool=pool,
                      warm_views=getattr(args, "warm_views", False),
                      **paged_kw)

    return make


def parse_roles(spec: str) -> list | None:
    """``--roles prefill=N,decode=M`` -> the per-replica role list
    (prefill replicas first — their fleet indices are stable, so
    TONY_SERVE_FAULTS addressing and log lines stay readable)."""
    if not spec.strip():
        return None
    counts = {"prefill": 0, "decode": 0}
    for part in spec.split(","):
        name, sep, n = part.strip().partition("=")
        if not sep or name not in counts:
            raise SystemExit(
                f"--roles expects 'prefill=N,decode=M', got {spec!r}")
        try:
            counts[name] = int(n)
        except ValueError:
            raise SystemExit(f"--roles count {n!r} is not an integer") \
                from None
    if counts["prefill"] < 1 or counts["decode"] < 1:
        raise SystemExit("--roles needs at least one prefill AND one "
                         "decode replica")
    return ["prefill"] * counts["prefill"] \
        + ["decode"] * counts["decode"]


def agent_argv(args, index: int) -> list:
    """The ``python -m tony_tpu.cli.replica`` argv mirroring this
    gateway's engine knobs — a launched agent must be configured
    exactly like an in-process replica would have been."""
    argv = ["--serve-batch", str(args.serve_batch),
            "--chunk-steps", str(args.chunk_steps),
            "--prefill-chunk-tokens",
            str(getattr(args, "prefill_chunk_tokens", 0)),
            "--prefix-cache-mb", str(args.prefix_cache_mb),
            "--kv-host-mb", str(getattr(args, "kv_host_mb", 0.0)),
            "--speculate-k", str(args.speculate_k),
            "--kv-page-size", str(args.kv_page_size),
            "--kv-pages", str(args.kv_pages),
            "--max-pending", str(args.max_pending),
            "--eos-id", str(args.eos_id),
            "--dtype", args.dtype,
            "--replica-index", str(index),
            # launched agents share THIS host: auto-sized KV pools
            # must divide its HBM by the fleet CEILING, exactly like
            # in-process replicas do (the PR-8 oversubscription rule)
            "--host-share", str(max(1, args.replicas,
                                    getattr(args, "autoscale_max", 0)
                                    or 0)),
            # crash-safety knobs (ISSUE-20): launched agents keep
            # orphans adoptable exactly as long as the gateway keeps
            # terminals resumable, and freeze in-flight slots after
            # --agent-grace of gateway silence
            "--park-ttl", str(getattr(args, "park_ttl", 60.0)),
            "--gateway-grace", str(getattr(args, "agent_grace", 0.0)),
            "--port", "0"]
    if getattr(args, "mesh", "").strip():
        argv += ["--mesh", args.mesh,
                 "--shard-rules", getattr(args, "shard_rules", "serve")]
    if getattr(args, "profile_dir", ""):
        # launched agents share THIS host: their /v1/profile captures
        # land under the gateway's profile dir, one subdir per agent
        argv += ["--profile-dir",
                 os.path.join(args.profile_dir, f"agent-{index}")]
    if args.no_paged_kv:
        argv.append("--no-paged-kv")
    if args.demo_model:
        argv.append("--demo-model")
    else:
        argv += ["--model", args.model]
    if getattr(args, "compile_cache", None) is not None:
        argv += ["--compile-cache", args.compile_cache]
    return argv


def remote_server_factory(args):
    """``make(index, hosts=None) -> RemoteServer`` — the remote twin
    of ``server_factory``. ``hosts`` is a provisioned slice's host
    list (``ProvisionerBackend.server_factory(hosts)`` — the grown
    remote mode): a ``host:port`` entry attaches to an agent already
    listening there (the slice's boot ran ``cli.replica``); a bare
    localhost entry (or no hosts — the dev/smoke shape) launches the
    agent as a local subprocess via ``launch_local_agent``.
    ``TONY_SERVE_FAULTS`` transport faults arm at the stub by fleet
    index while engine faults ride the launched agent's environment —
    one env var, both failure planes."""
    import tempfile

    from tony_tpu.gateway.remote import RemoteServer, launch_local_agent
    from tony_tpu.serve import FaultPlan

    def stub(address: str, index: int, proc=None) -> RemoteServer:
        return RemoteServer(
            address,
            heartbeat_interval_s=getattr(args, "agent_heartbeat", 1.0),
            lease_misses=getattr(args, "agent_lease_misses", 5),
            stall_timeout_s=args.stall_timeout,
            agent_channel=getattr(args, "agent_channel", "mux"),
            transport_faults=FaultPlan.transport_from_env(replica=index),
            agent_proc=proc)

    def make(index: int, hosts=None) -> RemoteServer:
        if hosts:
            h = str(hosts[0])
            if ":" in h:
                return stub(h, index)
            if h not in ("localhost", "127.0.0.1"):
                raise ValueError(
                    f"remote host {h!r} must either run `python -m "
                    f"tony_tpu.cli.replica` itself and be given as "
                    f"host:port, or be localhost (subprocess launch)")
        port_dir = tempfile.mkdtemp(prefix=f"tony-agent-{index}-")
        proc, address = launch_local_agent(
            agent_argv(args, index),
            port_file=os.path.join(port_dir, "agent.port"))
        try:
            return stub(address, index, proc=proc)
        except Exception:
            # the stub never existed, so nothing will ever close() it:
            # reap the agent here or a failed boot (bad engine, armed
            # boot fault) leaks a full engine's memory per attempt
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except Exception:  # noqa: BLE001 — best-effort teardown
                proc.kill()
            raise

    return make


def build_gateway(args, model, params, eos, *, metrics_store=None):
    """Servers + Gateway from parsed args (shared with tests/bench).
    Remote mode (``--agents`` attach / ``--remote-replica`` launch)
    ignores ``model``/``params`` — the agents own the weights and the
    gateway process is a pure router."""
    from tony_tpu.gateway import Gateway, GatewayHistory

    agents = [a.strip() for a in getattr(args, "agents", "").split(",")
              if a.strip()]
    # role split sizes the fleet itself: prefill=N,decode=M means
    # exactly N+M replicas, whatever --replicas said
    roles = parse_roles(getattr(args, "roles", ""))
    if roles:
        if agents and len(agents) != len(roles):
            raise SystemExit(
                f"--roles names {len(roles)} replicas but --agents "
                f"lists {len(agents)}")
        args.replicas = len(roles)
    # TONY_SERVE_FAULTS arms deterministic fault injection per replica
    # (serve/faults.py) — the chaos-smoke hook; unset = None = zero cost
    if agents:
        rmake = remote_server_factory(args)
        servers = [rmake(i, hosts=[addr])
                   for i, addr in enumerate(agents)]
    elif getattr(args, "remote_replica", False):
        rmake = remote_server_factory(args)
        servers = [rmake(i) for i in range(max(1, args.replicas))]
    else:
        make = server_factory(args, model, params, eos)
        servers = [make(i) for i in range(max(1, args.replicas))]
    armed = [i for i, s in enumerate(servers)
             if s.fault_plan is not None
             or getattr(s, "transport_faults", None) is not None]
    if armed:
        logging.getLogger(__name__).warning(
            "fault injection ARMED on replica(s) %s via TONY_SERVE_FAULTS",
            armed)
    history = None
    if args.history:
        history = GatewayHistory(args.history,
                                 n_replicas=len(servers))
    journal = None
    if getattr(args, "journal", False) or getattr(args, "recover",
                                                  False):
        # the WAL lands in THIS boot's history job dir (next to
        # requests.jsonl); --recover implies journaling — a recovered
        # gateway that did not journal would be unrecoverable itself
        if history is None:
            raise SystemExit("--journal/--recover need --history for "
                             "a place to put the journal")
        from tony_tpu.gateway.journal import TicketJournal

        journal = TicketJournal(
            os.path.join(history.job_dir, "journal.ndjson"),
            fsync=getattr(args, "journal_fsync", "batch"))
    trace_capacity = getattr(args, "trace_capacity", 256)
    return Gateway(servers, max_queue=args.max_queue,
                   default_ttl_s=args.default_ttl,
                   journal=journal,
                   park_ttl_s=getattr(args, "park_ttl", 60.0),
                   metrics_store=metrics_store, history=history,
                   max_attempts=args.max_attempts,
                   stall_timeout_s=args.stall_timeout,
                   breaker_base_s=args.breaker_base,
                   breaker_max_s=args.breaker_max,
                   quarantine_after=args.quarantine_after,
                   tracing=trace_capacity > 0,
                   trace_capacity=max(1, trace_capacity),
                   profile_dir=getattr(args, "profile_dir", "") or None,
                   tier_weights=getattr(args, "tier_weights", "") or None,
                   tenant_quota_rate=getattr(args, "tenant_quota", 0.0),
                   tenant_quota_burst=getattr(args, "tenant_burst", 0.0),
                   alerts=not getattr(args, "no_alerts", False),
                   alert_interval_s=getattr(args, "alert_interval", 1.0),
                   alert_thresholds={
                       "queue_wait_s": getattr(args, "alert_queue_wait",
                                               5.0),
                       "kv_free_frac": getattr(args,
                                               "alert_kv_free_frac",
                                               0.15),
                       "ttft_slo_s": getattr(args, "alert_ttft_slo",
                                             0.0),
                       "host_thrash_bytes": getattr(
                           args, "alert_host_thrash_bytes",
                           float(1 << 20)),
                       "shed_storm_count": getattr(
                           args, "alert_shed_storm", 50),
                       "shed_storm_window_s": getattr(
                           args, "alert_shed_window", 10.0),
                   },
                   bundle_on_alert=not getattr(args, "no_alert_bundles",
                                               False),
                   roles=roles,
                   prefix_affinity=not getattr(args,
                                               "no_prefix_affinity",
                                               False),
                   autotune=getattr(args, "autotune", False),
                   autotune_interval_s=getattr(args,
                                               "autotune_interval",
                                               1.0),
                   autotune_config={
                       "chunk_bounds": (
                           max(1, getattr(args, "autotune_chunk_min",
                                          1)),
                           getattr(args, "autotune_chunk_max", 32)),
                       "spec_bounds": (
                           0, getattr(args, "autotune_spec_max", 16)),
                       "prefill_bounds": (
                           0, getattr(args, "autotune_prefill_max",
                                      0)),
                       "hold_ticks": getattr(args, "autotune_hold", 2),
                       "cooldown_ticks": getattr(
                           args, "autotune_cooldown", 3),
                   } if getattr(args, "autotune", False) else None)


def build_scaler(args, gateway, model, params, eos):
    """Arm the elastic autoscaler when --autoscale-max asks for one:
    a ThreadBackend over the same server factory boot replicas used
    (weights shared — scale-up costs one KV cache + the probe's
    compile, not a checkpoint load). Returns None when not armed."""
    max_replicas = getattr(args, "autoscale_max", 0)
    if not max_replicas:
        return None
    if getattr(args, "roles", "").strip():
        # a scaler-minted replica would need a role assignment policy
        # (grow which pool?) this PR does not take a position on —
        # refuse loudly instead of growing a roleless generalist into
        # a fleet whose routing would never send it work
        raise SystemExit("--autoscale-max cannot be combined with "
                         "--roles (fixed role-split fleets only)")
    from tony_tpu.gateway import AutoScaler, ThreadBackend

    boot = max(1, args.replicas)
    if max_replicas < boot:
        raise SystemExit(f"--autoscale-max {max_replicas} is below "
                         f"--replicas {boot}")
    floor = max(1, getattr(args, "autoscale_min", 0) or boot)
    if floor > max_replicas:
        raise SystemExit(f"--autoscale-min {floor} is above "
                         f"--autoscale-max {max_replicas}")
    # a dynamic replica's fleet index is wherever the (append-only)
    # replica list currently ends — read at create time, so a failed
    # create/join cannot desync TONY_SERVE_FAULTS addressing for the
    # replicas that come after it (only the scaler thread creates, so
    # the read cannot race another add)
    if getattr(args, "agents", "").strip():
        raise SystemExit(
            "--autoscale-max cannot mint new agents in --agents attach "
            "mode (the fleet is the given list); use --remote-replica "
            "launch mode or a provisioner backend")
    if getattr(args, "remote_replica", False):
        rmake = remote_server_factory(args)
        backend = ThreadBackend(
            lambda: rmake(len(gateway.replicas)), label="remote-agent")
    else:
        make = server_factory(args, model, params, eos)
        backend = ThreadBackend(lambda: make(len(gateway.replicas)))
    return AutoScaler(
        gateway, backend,
        min_replicas=floor,
        max_replicas=max_replicas,
        interval_s=getattr(args, "autoscale_interval", 1.0),
        up_queue_depth=getattr(args, "autoscale_up_queue", 4.0),
        up_wait_s=getattr(args, "autoscale_up_wait", 1.0),
        ttft_slo_s=getattr(args, "autoscale_ttft_slo", 0.0),
        cooldown_up_s=getattr(args, "autoscale_cooldown_up", 5.0),
        cooldown_down_s=getattr(args, "autoscale_cooldown_down", 30.0),
        drain_timeout_s=getattr(args, "drain_timeout", 120.0))


def build_rebalancer(args, gateway):
    """Arm the pressure-driven rebalancer when --rebalance asks for
    one (--no-rebalance wins: it is the A/B control in smoke runs
    that pass both). Returns None when not armed."""
    if getattr(args, "no_rebalance", False) \
            or not getattr(args, "rebalance", False):
        return None
    from tony_tpu.gateway import Rebalancer

    cooldown = getattr(args, "rebalance_cooldown", 5.0)
    return Rebalancer(
        gateway,
        interval_s=getattr(args, "rebalance_interval", 1.0),
        skew_frac=getattr(args, "rebalance_skew", 0.5),
        stable=getattr(args, "rebalance_stable", 2),
        cooldown_s=cooldown,
        fail_cooldown_s=2 * cooldown)


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    remote = bool(args.agents.strip()) or args.remote_replica
    if not args.model and not args.demo_model and not args.agents:
        parser.error("one of --model / --demo-model / --agents is "
                     "required")
    if args.remote_replica and not (args.model or args.demo_model):
        parser.error("--remote-replica needs --model or --demo-model "
                     "to hand to the launched agents")
    logging.basicConfig(level=logging.INFO)
    if args.compile_cache != "" and not remote:
        # a pure router compiles nothing: the agents arm their own
        from tony_tpu.utils import compilecache

        compilecache.enable(args.compile_cache)

    encode = decode = None
    model = params = None
    eos: list = []
    if remote:
        # the gateway process is a pure router: the agents own the
        # weights (and pay the compiles). With a checkpoint named, load
        # ONLY the tokenizer so text prompts still work at the door.
        if args.model:
            try:
                import transformers

                tok = transformers.AutoTokenizer.from_pretrained(
                    args.model)
                encode, decode = tok.encode, tok.decode
            except Exception:  # noqa: BLE001 — token_ids still serve
                print("note: no tokenizer in model dir; token_ids "
                      "requests only", file=sys.stderr)
    elif args.demo_model:
        model, params, eos = *demo_model(), \
            ([args.eos_id] if args.eos_id >= 0 else [])
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
        try:
            import transformers

            tok = transformers.AutoTokenizer.from_pretrained(args.model)
            encode, decode = tok.encode, tok.decode
        except Exception:  # noqa: BLE001 — a checkpoint without a
            # tokenizer still serves token_ids requests
            print("note: no tokenizer in model dir; token_ids "
                  "requests only", file=sys.stderr)
    return serve(args, model, params, eos, encode=encode, decode=decode)


def serve(args, model, params, eos, *, encode=None, decode=None,
          on_ready=None) -> int:
    """Everything after the weights are in hand: build the fleet, open
    the HTTP front end, print the address, then block until
    SIGTERM/SIGINT and drain. Returns the process exit code. Must run
    on the main thread (it installs the signal handlers).
    ``on_ready(http)`` is called once the listener is up — how an
    embedding caller (``chip_smoke.py`` serves a seeded random model
    this way) learns the ephemeral port."""
    from tony_tpu.gateway import GatewayEdge, GatewayHTTP
    from tony_tpu.metrics import MetricsStore

    # --recover: find the DEAD boot's journal BEFORE build_gateway
    # creates this boot's (fresh, newest-mtime) one — the replay must
    # see the previous incarnation's record, not our empty file
    recover_entries = None
    if getattr(args, "recover", False):
        from tony_tpu.gateway import journal as journal_mod

        prev = journal_mod.find_latest(args.history) \
            if args.history else None
        recover_entries = journal_mod.replay(prev) if prev else {}
        n_live = sum(1 for e in recover_entries.values() if e.live)
        print(f"recovery: replayed "
              f"{prev or '(no previous journal)'} — "
              f"{n_live} live request(s)", file=sys.stderr, flush=True)

    gateway = build_gateway(args, model, params, eos,
                            metrics_store=MetricsStore()).start()
    if recover_entries is not None:
        report = gateway.recover_from_journal(recover_entries)
        print(f"recovery: {report['adopted']} adopted mid-stream, "
              f"{report['rerun']} re-run from prompt, "
              f"{report['finished']} finished results, "
              f"{report['shed']} shed "
              f"({report.get('wall_ms', 0):.0f}ms)",
              file=sys.stderr, flush=True)
    scaler = build_scaler(args, gateway, model, params, eos)
    if scaler is not None:
        scaler.start()
    rebalancer = build_rebalancer(args, gateway)
    if rebalancer is not None:
        rebalancer.start()
    if getattr(args, "edge", "event") == "event":
        http = GatewayEdge(
            gateway, host=args.host, port=args.port,
            encode=encode, decode=decode,
            max_connections=args.edge_max_connections,
            workers=args.edge_workers,
            write_buffer_kb=args.edge_write_buffer_kb,
            drain_timeout_s=args.edge_drain_timeout,
            io_timeout_s=args.edge_io_timeout).start()
    else:
        http = GatewayHTTP(gateway, host=args.host, port=args.port,
                           encode=encode, decode=decode).start()
    elastic = "" if scaler is None else \
        (f", autoscale {scaler.min_replicas}-{scaler.max_replicas}")
    if rebalancer is not None:
        elastic += ", rebalance on"
    n_rep = len(gateway.replicas)
    mode = ""
    if bool(args.agents.strip()) or args.remote_replica:
        mode = " remote agents: " + ", ".join(
            r.host for r in gateway.replicas)
    print(f"tony-tpu gateway at http://{http.host}:{http.port} "
          f"({n_rep} replica(s) x {args.serve_batch} "
          f"slots{elastic}{mode})", flush=True)

    stop = threading.Event()

    def _on_signal(signum, frame):
        if stop.is_set():  # second signal: force exit
            os._exit(1)
        print(f"signal {signum}: draining (readyz -> 503, finishing "
              f"in-flight)...", file=sys.stderr, flush=True)
        stop.set()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    if on_ready is not None:
        on_ready(http)
    stop.wait()
    ok = gateway.drain(timeout=args.drain_timeout)
    http.stop()
    if not ok:
        print("drain timed out with requests still in flight",
              file=sys.stderr)
        return 1
    print("drained clean", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
