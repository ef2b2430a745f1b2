# Build/verify entry points (reference parity: the gradle build's
# check/test wiring, build.gradle:113-116 + .circleci/config.yml).
#
#   make lint   - static analysis: ruff when installed AND the in-tree
#                 AST checker (tools/lint.py) — ruff alone would let
#                 the in-tree rules drift on boxes that have it, and
#                 vice versa; tools/serve_smoke.sh runs the same gate
#                 at its top so smoke runs fail fast on lint drift
#   make smoke  - <60 s unit tier (no jax-heavy model/e2e suites):
#                 config, session, scheduler, rpc, events, utils,
#                 remotefs, runtimes, workflow, tpu_info, compilecache,
#                 proxy, profiler
#   make check  - lint + smoke (the pre-commit gate)
#   make test   - the full suite (~15-20 min on a 1-core box)
#   make serve-smoke - boot a tiny-model gateway, concurrent curl
#                 clients (unary + streaming), a /metrics exposition +
#                 /debug/trace + on-demand profile observability round,
#                 SIGTERM drain; every phase `timeout`-bounded so a
#                 hang exits nonzero
#   make chaos-smoke - just the fault-injection round of serve-smoke:
#                 a 2-replica gateway with replica 0's dispatches
#                 killed via TONY_SERVE_FAULTS must keep serving
#                 (failover, zero 5xx) and rejoin the dead replica
#   make autoscale-smoke - just the elastic round of serve-smoke:
#                 burst load at a min=1/max=3 gateway must scale up
#                 (probe-admitted), serve with zero 5xx, and drain
#                 back to the floor once idle

PY ?= python

LINT_PATHS = tony_tpu tests examples tools __graft_entry__.py

SMOKE_TESTS = tests/test_config.py tests/test_session.py \
	tests/test_scheduler.py tests/test_rpc.py tests/test_events.py \
	tests/test_utils.py tests/test_remotefs.py tests/test_runtimes.py \
	tests/test_workflow.py tests/test_tpu_info.py \
	tests/test_compilecache.py tests/test_proxy.py tests/test_profiler.py

#   make goodput-smoke - just the goodput/alerts round of serve-smoke:
#                 a tiny KV page pool under load must fire a
#                 kv_pages_pressure alert (visible on /stats, in
#                 history alerts.jsonl, and on the portal), resolve
#                 once idle, and /debug/goodput must name the largest
#                 waste bucket
#   make remote-smoke - just the remote-replica round of serve-smoke:
#                 2 replica-agent subprocesses behind an --agents
#                 gateway; kill -9 one mid-run -> zero 5xx, outputs
#                 token-exact vs a local-replica control, the corpse
#                 quarantined, the survivor SIGTERM-drained clean;
#                 plus (ISSUE-15) the survivor's dispatch counts and a
#                 non-null merged goodput block on /stats,
#                 tony_goodput_fraction + tony_transport_clock_offset_ms
#                 on /metrics, and a /debug/profile fan-out capture on
#                 the survivor agent
#   make bundle-smoke - just the flight-recorder round of serve-smoke:
#                 a live subprocess gateway with --history and a
#                 synthetic queue_aging alert must dump one
#                 self-contained debug bundle (alerts, traces,
#                 per-replica dispatch/goodput blocks, signals) into
#                 <job dir>/bundles/, validated as JSON; GET
#                 /debug/bundle must serve the same document shape

#   make disagg-smoke - just the disaggregation round of serve-smoke:
#     a --roles prefill=1,decode=1 gateway with chunked prefill and a
#     host-RAM KV page tier under mixed long-prompt/short-chat traffic
#     -> zero 5xx, token-exact vs a single-pool control, host-tier
#     page-ins and multi-chunk prefills visible on /stats

#   make autotune-smoke - just the shape-controller round of
#     serve-smoke: an --autotune gateway booted at chunk-steps 1 under
#     mixed traffic must actuate (grow chunk depth off the goodput
#     ledger), stay token-exact vs a static control gateway with zero
#     5xx, converge once idle, and land the decision in /stats
#     engine.autotune + tony_autotune_* metrics + history
#     metrics/autotune.jsonl

#   make shard-smoke - just the sharded-replica round of serve-smoke:
#     a --mesh 4 gateway on 4 virtual CPU devices (params sharded on
#     output dims, KV page pools sharded 4-way on the kv-head axis)
#     under greedy/sampled/prefix/streaming traffic, byte-identical
#     outputs vs a single-device control gateway, mesh topology +
#     per-chip pricing on /stats engine.mesh + tony_mesh_* metrics
#   make storm-smoke - just the connection-storm round of serve-smoke:
#     tools/storm.py parks 500 idle keep-alive connections on an
#     event-edge gateway, then fires 2000 concurrent NDJSON streams
#     in bursts — zero shed / zero unintentional 5xx, token-exact
#     spot checks vs unary controls, edge block on /stats +
#     tony_edge_* on /metrics, clean SIGTERM drain
#   make migrate-smoke - just the live-migration round of serve-smoke:
#     two replicas leasing ONE shared PagePool, remove_replica freezes
#     a throttled in-flight stream mid-decode and the survivor adopts
#     it by owner swap — token-exact vs a no-migration control, zero
#     5xx, zero KV pages copied, retiring drain bounded by freeze
#     cost instead of the stream's remaining decode budget
#   make rebalance-smoke - just the rebalancer round of serve-smoke:
#     three live streams piled onto one replica of a two-engine
#     shared-pool fleet, the Rebalancer detects the occupancy skew
#     and autonomously migrates a session to the idle replica —
#     token-exact vs no-rebalance controls, zero 5xx, the decision
#     trail in the gateway history's metrics/rebalance.jsonl
#   make recovery-smoke - just the crash-recovery round of serve-smoke:
#     a --journal gateway over two agent subprocesses is kill -9'd
#     mid-stream, the agents park the orphans after --gateway-grace,
#     a --recover boot replays the WAL and adopts them token-exact
#     (zero re-prefill), every stream re-fetched byte-identical via
#     GET /v1/stream/<id>?offset=0 vs a never-crashed control — zero
#     5xx after restart, clean drain compacts the journal to empty

.PHONY: lint smoke check test serve-smoke chaos-smoke \
	autoscale-smoke goodput-smoke remote-smoke disagg-smoke \
	autotune-smoke shard-smoke bundle-smoke storm-smoke \
	migrate-smoke rebalance-smoke recovery-smoke

lint:
	@if command -v ruff >/dev/null 2>&1; then \
		echo "ruff check"; ruff check $(LINT_PATHS) || exit 1; \
	else \
		echo "(no ruff in image — in-tree checker only)"; \
	fi
	@echo "tools/lint.py"
	@$(PY) tools/lint.py $(LINT_PATHS)

smoke:
	$(PY) -m pytest $(SMOKE_TESTS) -q -p no:cacheprovider

check: lint smoke

test:
	$(PY) -m pytest tests/ -q

serve-smoke:
	PY=$(PY) sh tools/serve_smoke.sh

chaos-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=chaos sh tools/serve_smoke.sh

autoscale-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=autoscale sh tools/serve_smoke.sh

goodput-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=goodput sh tools/serve_smoke.sh

remote-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=remote sh tools/serve_smoke.sh

disagg-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=disagg sh tools/serve_smoke.sh

autotune-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=autotune sh tools/serve_smoke.sh

shard-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=shard sh tools/serve_smoke.sh

bundle-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=bundle sh tools/serve_smoke.sh

storm-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=storm sh tools/serve_smoke.sh

migrate-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=migrate sh tools/serve_smoke.sh

rebalance-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=rebalance sh tools/serve_smoke.sh

recovery-smoke:
	PY=$(PY) SERVE_SMOKE_ROUNDS=recovery sh tools/serve_smoke.sh
