"""Sharded serving (ISSUE-14): tensor-sharded replicas on the virtual
CPU mesh must be BYTE-IDENTICAL to single-chip replicas.

The exactness argument is structural (parallel.sharding "serve"
preset): weights shard only on OUTPUT dims (the row-parallel o/wo
kernels flip to embed), the model pins activations replicated at those
boundaries (``TransformerConfig.shard_activations``), so every float
reduction runs whole on one chip in the single-chip order and all
cross-chip traffic is all-gather — pure data movement. These tests pin
the consequence: token streams, dispatch counts, prefill counts, and
speculation/prefix counters all equal mesh=1 vs mesh=4, across paged x
unpaged x greedy x seeded-sampling x speculation x prefix hits x
chunked prefill x handoff x host tier. Plus the capacity-unlock math
(a footprint that exceeds one chip fits per-chip under the mesh) and
the per-chip goodput pricing."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.parallel.mesh import MeshSpec, make_mesh
from tony_tpu.serve import Request, Server

pytestmark = pytest.mark.skipif(jax.device_count() < 4,
                                reason="needs 4 virtual devices")


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def mesh4():
    return make_mesh(MeshSpec(data=1, tensor=4),
                     devices=jax.devices()[:4])


def _workload():
    """Greedy + seeded sampling + an exact prefix repeat + a
    repetitive prompt the prompt-lookup drafter hits on."""
    rep = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7, 8]
    return [
        Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=12, id="greedy"),
        Request(prompt=rep, max_new_tokens=10, id="spec"),
        Request(prompt=[3, 1, 4, 1, 5, 9, 2, 6], max_new_tokens=8,
                temperature=0.8, top_k=8, seed=123, id="sampled"),
        Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=12, id="hit"),
    ]


def _run(tiny, mesh, paged, **kw):
    model, params = tiny
    kw.setdefault("batch_size", 3)
    kw.setdefault("chunk_steps", 4)
    kw.setdefault("prefix_cache_mb", 8)
    kw.setdefault("speculate_k", 4)
    s = Server(model, params, paged=paged, mesh=mesh, **kw)
    out = {}
    for r in s.run(_workload()):
        out[r.id] = list(r.tokens)
    return out, s


@pytest.mark.parametrize("paged", [True, False])
def test_token_exact_mesh4_vs_single_chip(tiny, mesh4, paged):
    """THE gate: byte-identical streams AND identical dispatch/prefill
    counts (no new host syncs, no extra dispatches) on the full mixed
    workload — greedy, seeded sampling, speculation, prefix hits."""
    a, sa = _run(tiny, None, paged)
    b, sb = _run(tiny, mesh4, paged)
    assert a == b
    assert sa.dispatches == sb.dispatches
    assert sa.prefills == sb.prefills
    assert sa.steps == sb.steps
    # speculation + prefix behavior identical, not just outputs
    assert sa.spec_drafted == sb.spec_drafted
    assert sa.spec_accepted == sb.spec_accepted
    assert sa.prefix_hits == sb.prefix_hits
    assert sb.kv_shards == 4


def test_mesh1_is_the_trivial_shard(tiny):
    """A 1-device mesh is the degenerate sharded path — same streams,
    same counters (the smoke control's A/B anchor)."""
    mesh1 = make_mesh(MeshSpec(data=1, tensor=1),
                      devices=jax.devices()[:1])
    a, _ = _run(tiny, None, True)
    b, sb = _run(tiny, mesh1, True)
    assert a == b
    assert sb.mesh_info()["devices"] == 1


def test_pools_stay_sharded_across_serving(tiny, mesh4):
    """The KV pools must KEEP their kv-head sharding through admits,
    decode chunks, verify rounds and evictions — a silent gather would
    quietly forfeit the capacity unlock."""
    _, s = _run(tiny, mesh4, True)
    found = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            s.slots.cache)[0]:
        name = str(path[-1].key if hasattr(path[-1], "key")
                   else path[-1])
        if name in ("cached_key", "cached_value"):
            found += 1
            spec = tuple(leaf.sharding.spec)
            assert "tensor" in spec, (name, spec)
    assert found >= 4  # k + v per layer
    # and every writer consumed the sharded tree it was given: the
    # donation holds under the pools' NamedShardings too
    c = s.counters()
    assert c["kv_tree_kept"] == 0 and c["kv_tree_donated"] > 0, c


def test_scan_layers_int8_kv_sharded_parity(tiny, mesh4):
    """The stacked-layers + int8-KV cell: scan params carry a leading
    layers axis (the serve preset must place it whole) and the int8
    scale leaves shard alongside their pools."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32, scan_layers=True,
                            kv_cache_quant=True, positional="learned",
                            norm="layer", use_bias=True,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    reqs = lambda: [Request(prompt=[1, 2, 3, 4], max_new_tokens=8,
                            id="a"),
                    Request(prompt=[9, 8, 7], max_new_tokens=6,
                            temperature=0.7, top_k=8, seed=7, id="b")]
    outs = []
    for mesh in (None, mesh4):
        s = Server(model, params, batch_size=2, chunk_steps=2,
                   paged=True, mesh=mesh)
        outs.append({r.id: list(r.tokens) for r in s.run(reqs())})
    assert outs[0] == outs[1]


def test_chunked_prefill_sharded_parity(tiny, mesh4):
    """Chunked prefill under the mesh: same chunk count, same slot
    state, same stream."""
    model, params = tiny
    long_prompt = list(range(1, 41))
    outs, chunks = [], []
    for mesh in (None, mesh4):
        s = Server(model, params, batch_size=2, prefill_chunk_tokens=16,
                   paged=True, mesh=mesh)
        res = list(s.run([Request(prompt=long_prompt,
                                  max_new_tokens=6, id="long")]))
        outs.append([list(r.tokens) for r in res])
        chunks.append((res[0].prefill_chunks,
                       s.prefill_chunk_dispatches))
    assert outs[0] == outs[1]
    assert chunks[0] == chunks[1]
    assert chunks[0][0] >= 2  # actually chunked


def test_handoff_between_sharded_engines(tiny, mesh4):
    """The disaggregation handoff under the mesh: the page-list
    payload is a SHARDED pytree gathered on the prefill engine and
    scattered into the decode engine's sharded pools — streams equal a
    generalist single-chip engine."""
    model, params = tiny
    prompt = [5, 4, 3, 2, 1, 6, 7]
    control = Server(model, params, batch_size=2, paged=True)
    want = [list(r.tokens) for r in control.run(
        [Request(prompt=prompt, max_new_tokens=8, seed=3,
                 temperature=0.6, top_k=8, id="x")])]

    pre = Server(model, params, batch_size=2, paged=True, mesh=mesh4)
    dec = Server(model, params, batch_size=2, paged=True, mesh=mesh4)
    (h,) = list(pre.run([Request(prompt=prompt, max_new_tokens=8,
                                 prefill_only=True, id="x")]))
    assert h.finish_reason == "handoff"
    got = [list(r.tokens) for r in dec.run(
        [Request(prompt=prompt, max_new_tokens=8, seed=3,
                 temperature=0.6, top_k=8, handoff=h.handoff,
                 id="x")])]
    assert got == want
    assert pre.handoffs_out == 1 and dec.handoffs_in == 1


def test_host_tier_spill_page_in_sharded(tiny, mesh4):
    """Host-tier round trip under the mesh: spilled pages gather from
    sharded pools to host RAM and scatter back bitwise — streams equal
    the unsharded tier engine's."""
    model, params = tiny
    p1 = list(range(1, 17))
    p2 = list(range(20, 36))
    reqs = lambda: [Request(prompt=p, max_new_tokens=4, id=f"r{i}")
                    for i, p in enumerate([p1, p2, p1])]
    outs, tiers = [], []
    for mesh in (None, mesh4):
        s = Server(model, params, batch_size=2, paged=True,
                   prefix_cache_mb=0.02, kv_host_mb=4, mesh=mesh)
        outs.append({r.id: list(r.tokens) for r in s.run(reqs())})
        tiers.append(s.host_tier.stats()["spills"])
    assert outs[0] == outs[1]
    assert tiers[0] == tiers[1]
    assert tiers[0] > 0  # the tiny store actually churned


def test_capacity_unlock_math(tiny, mesh4):
    """The reason this PR exists: a param+KV footprint that does NOT
    fit one chip fits per-chip under the mesh — demonstrated via the
    same worst-case byte accounting admission uses, on an engine that
    then actually serves end-to-end."""
    _, s = _run(tiny, mesh4, True)
    info = s.mesh_info()
    total = info["param_bytes_total"] + info["kv_bytes_total"]
    per_chip = info["param_bytes_per_chip"] + info["kv_bytes_per_chip"]
    # pick the notional per-chip HBM budget between the two: one chip
    # could NOT hold the model, the 4-chip mesh holds it with room
    budget = (total + per_chip) // 2
    assert total > budget > per_chip
    assert info["kv_shards"] == 4
    # and the engine genuinely served the workload sharded
    assert s.dispatches > 0 and s.prefills > 0


def test_per_chip_goodput_pricing(tiny, mesh4):
    """The goodput satellite: the cost model prices dispatches with
    PER-CHIP bytes/FLOPs (vs the single-chip roofline), the ledger
    still reconciles, and counters carry the topology."""
    _, single = _run(tiny, None, True)
    _, s = _run(tiny, mesh4, True)
    # per-chip param bytes are the sharded residency, not the total
    assert s.cost.param_bytes == s.mesh_info()["param_bytes_per_chip"]
    assert s.cost.param_bytes < single.cost.param_bytes
    # KV bytes/token divide by the pool shard count
    assert s.cost.kv_token_bytes == pytest.approx(
        single.cost.kv_token_bytes / 4)
    # attention work splits with the pools
    assert s.cost.n_heads == single.cost.n_heads // 4
    # a decode dispatch estimate is ~1/4 the single-chip estimate
    nb1, fl1 = single.cost.decode(4, 3, 64)
    nb4, fl4 = s.cost.decode(4, 3, 64)
    assert nb4 < nb1 and fl4 < fl1
    # the ledger still holds its structural invariant sharded
    g = s.goodput()
    assert sum(g["buckets"].values()) <= 1.0 + 1e-9
    # flat counters carry the topology (MetricsStore + agent wire)
    c = s.counters()
    assert c["mesh_devices"] == 4
    assert c["mesh_kv_shards"] == 4
    assert c["mesh_param_bytes_per_chip"] == s.cost.param_bytes


def test_flash_decode_refused_under_mesh(tiny, mesh4):
    model, params = tiny
    cfg = dataclasses.replace(model.cfg, decode_attention="flash")
    with pytest.raises(NotImplementedError, match="flash"):
        Server(Transformer(cfg), params, batch_size=2, mesh=mesh4)


def test_gateway_sharded_stats_and_metrics(tiny, mesh4):
    """The fleet surfaces: /stats engine.mesh topology + per-replica
    mesh block + tony_mesh_* on the prom render."""
    from tony_tpu.gateway import Gateway, GenRequest
    from tony_tpu.obs.export import prometheus_text

    model, params = tiny
    servers = [Server(model, params, batch_size=2, mesh=mesh4)]
    gw = Gateway(servers, max_queue=16).start()
    try:
        tickets = [gw.submit(GenRequest([1 + i, 2, 3],
                                        max_new_tokens=4, id=i))
                   for i in range(3)]
        for t in tickets:
            t.result(timeout=120)
        snap = gw.snapshot()
        mesh = snap["engine"]["mesh"]
        assert mesh["enabled"] and mesh["devices"] == 4
        assert mesh["kv_shards"] == 4
        assert mesh["topology"] == {"tensor": 4}
        row = snap["replicas"][0]
        assert row["mesh"]["devices"] == 4
        assert row["mesh_devices"] == 4  # flat twin for MetricsStore
        text = prometheus_text(gw)
        assert "tony_mesh_enabled 1" in text
        assert "tony_mesh_devices 4" in text
        assert "tony_mesh_kv_shards 4" in text
    finally:
        gw.drain(timeout=60)
