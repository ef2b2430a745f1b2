"""Continuous-batching serving loop (tony_tpu.serve).

The exactness anchor: a request served through the slot scheduler —
including a slot evicted on EOS and re-admitted with a new prompt —
must produce token-for-token the same output as a solo ``generate()``
of that prompt. Scheduler invariants (admit/evict bookkeeping, chunk
overshoot trim, per-request rng isolation) ride along. CPU-only; the
per-slot decode path runs the same einsum attention as the scalar
path, so parity is exact, not approximate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig, generate
from tony_tpu.serve import Request, Server, SlotCache, bucket_len


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=32,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _solo(model, params, prompt, n, eos_id=-1):
    out = generate(model, params, jnp.asarray([prompt], jnp.int32),
                   max_new_tokens=n, eos_id=eos_id)
    return np.asarray(out)[0].tolist()


def _solo_trimmed(model, params, prompt, n, eos_ids):
    """Solo generate, cut at the first eos INCLUSIVE (serve reports up
    to and including the stop token; generate freezes past it)."""
    toks = _solo(model, params, prompt, n,
                 eos_id=list(eos_ids) if eos_ids else -1)
    for i, t in enumerate(toks):
        if t in eos_ids:
            return toks[:i + 1]
    return toks


@pytest.fixture(scope="module")
def eos_probe(tiny):
    """(prompt, its 8 greedy tokens, eos id, index of eos) where the
    eos id is FIRST emitted mid-sequence — so an engine stopping on it
    strikes after real decoding. Which prompt has that property
    depends on the seeded weights, hence on the installed jax's
    initialisers: search seeded 6-token prompts instead of hard-coding
    one (every candidate shares one compiled generate program)."""
    model, params = tiny
    rng = np.random.default_rng(0)
    for _ in range(64):
        prompt = rng.integers(1, 64, size=6).tolist()
        solo = _solo(model, params, prompt, 8)
        hit = next(((t, i) for i, t in enumerate(solo)
                    if i > 0 and t not in solo[:i]), None)
        if hit is not None:
            return prompt, solo, hit[0], hit[1]
    pytest.fail("no seeded prompt emits a new token mid-sequence")


def test_mixed_length_batch_matches_solo(tiny):
    """Mixed-length prompts through 2 slots == per-prompt solo decodes,
    token for token (the continuous-batching correctness anchor)."""
    model, params = tiny
    # three DISTINCT lengths: each solo generate compiles its own
    # prefill, so more lengths buy little extra coverage per second
    prompts = [[1, 2, 3], [5, 9], [17, 46, 10, 20, 62, 26]]
    server = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8)
    results = {r.id: r for r in server.run(
        Request(p, max_new_tokens=6) for p in prompts)}
    assert len(results) == len(prompts)
    for i, p in enumerate(prompts):
        assert results[i].tokens == _solo(model, params, p, 6), p
        assert results[i].finish_reason == "length"
        assert results[i].prompt == p


def test_slot_reuse_after_eos_exact(tiny, eos_probe):
    """A slot evicted on EOS and re-admitted with a new prompt produces
    token-for-token the same output as a solo generate() of that
    prompt — stale cache content must never leak into the new tenant."""
    model, params = tiny
    probe, solo, eos, idx = eos_probe
    follower = [7, 2, 5, 11, 4]
    server = Server(model, params, batch_size=1, eos_id=eos, min_bucket=8)
    res = {r.id: r for r in server.run([
        Request(probe, max_new_tokens=8, id="first"),
        Request(follower, max_new_tokens=6, id="reused"),
    ])}
    assert res["first"].tokens == solo[:idx + 1]
    assert res["first"].finish_reason == "eos"
    # batch_size=1: "reused" decodes in the SAME slot "first" vacated
    assert res["reused"].tokens == _solo_trimmed(model, params, follower,
                                                 6, (eos,))


def test_chunk_size_does_not_change_results(tiny, eos_probe):
    """chunk_steps only trades dispatches for latency: results are
    identical at 1 (token-at-a-time) and 8 (overshoot + trim)."""
    model, params = tiny
    probe, _, eos, _ = eos_probe
    reqs = [Request(probe, max_new_tokens=8, id="a"),
            Request([5, 9], max_new_tokens=7, id="b"),
            Request([3, 3, 3, 3], max_new_tokens=5, id="c")]
    import copy

    out = {}
    for chunk in (1, 8):
        server = Server(model, params, batch_size=2, eos_id=eos,
                        min_bucket=8, chunk_steps=chunk)
        out[chunk] = {r.id: (r.tokens, r.finish_reason)
                      for r in server.run(copy.deepcopy(reqs))}
    assert out[1] == out[8]


@pytest.mark.parametrize(
    "paged",
    # the unpaged cell rides the slow lane: unpaged frozen behavior is
    # already pinned tier-1 by the mid-chunk-EOS/refill and
    # overshoot-zero tests, and the paged cell compiles a superset of
    # the machinery (paged_view/write_back under freeze)
    [pytest.param(False, marks=pytest.mark.slow), True])
def test_frozen_chunk_invariance_1_vs_16(tiny, eos_probe, paged):
    """The ISSUE-13 chunk-invariance pin, extended to the frozen-slot
    variant: with in-dispatch EOS a chunk_steps=16 engine — deeper
    than every request's budget, so EVERY finishing slot freezes
    mid-chunk — is token-exact vs chunk_steps=1, across mixed EOS and
    budget finishes, paged and unpaged, with zero overshoot and the
    trim walk clean (freeze_faults == 0). Sampled co-tenants pin that
    frozen rows stop advancing rng without moving live draw chains."""
    model, params = tiny
    probe, _, eos, _ = eos_probe
    reqs = [Request(probe, max_new_tokens=8, id="a"),
            Request([5, 9], max_new_tokens=13, id="b"),
            Request([3, 3, 3, 3], max_new_tokens=5, id="c"),
            Request([9, 9, 2], max_new_tokens=7, temperature=0.9,
                    top_k=8, seed=5, id="s")]
    import copy

    out, servers = {}, {}
    for chunk in (1, 16):
        server = Server(model, params, batch_size=2, eos_id=eos,
                        min_bucket=8, chunk_steps=chunk, paged=paged)
        out[chunk] = {r.id: (r.tokens, r.finish_reason)
                      for r in server.run(copy.deepcopy(reqs))}
        servers[chunk] = server
    assert out[1] == out[16]
    deep = servers[16]
    assert deep.wasted_steps == 0
    assert deep.frozen_steps > 0  # budget-5 slot froze inside k=16...
    assert deep.freeze_faults == 0  # ...and re-emitted only its final


@pytest.mark.slow  # two scan_layers+int8 engine compiles; slow lane
def test_frozen_decode_scan_layers_int8(tiny):
    """In-dispatch EOS over a scan_layers + int8-KV engine (stacked
    [n_layers] cache counters broadcast the frozen sentinel writes,
    scale leaves drop them too) with speculation riding along —
    token-exact vs a solo generate(), only rejected drafts wasted,
    tail walk clean."""
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference",
                            scan_layers=True, kv_cache_quant=True)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    reqs = [Request([1, 2, 3, 4] * 3, max_new_tokens=11, id="rep"),
            Request([7, 9, 11], max_new_tokens=4, id="short")]
    # paged auto-downgrades nothing here (no sliding window):
    # exercise the paged default
    server = Server(model, params, batch_size=2, eos_id=-1,
                    min_bucket=8, chunk_steps=8, speculate_k=3)
    got = {r.id: r.tokens for r in server.run(reqs)}
    for r in reqs:
        assert got[r.id] == _solo(model, params, r.prompt,
                                  r.max_new_tokens), r.id
    assert server.wasted_steps == server.spec_drafted \
        - server.spec_accepted  # only rejected drafts
    assert server.freeze_faults == 0


def test_mid_chunk_eos_refill_parity(tiny, eos_probe):
    """A slot that samples EOS mid-chunk freezes in-dispatch, is
    evicted by the trim walk, and its slot refills from the queue the
    same scheduler round — the waiting request's output must be
    token-exact vs a solo generate() (stale frozen re-emits must never
    leak into the next tenant), with zero wasted steps end to end."""
    model, params = tiny
    probe, solo, eos, idx = eos_probe
    followers = [[7, 2, 5, 11, 4], [1, 6, 3], [44, 2, 9, 13]]
    server = Server(model, params, batch_size=2, eos_id=eos,
                    min_bucket=8, chunk_steps=8)
    reqs = [Request(probe, max_new_tokens=8, id="eos-mid")] + [
        Request(f, max_new_tokens=6, id=f"f{i}")
        for i, f in enumerate(followers)]
    res = {r.id: r for r in server.run(reqs)}
    assert res["eos-mid"].tokens == solo[:idx + 1]
    assert res["eos-mid"].finish_reason == "eos"
    for i, f in enumerate(followers):
        assert res[f"f{i}"].tokens == _solo_trimmed(
            model, params, f, 6, (eos,)), f
    assert server.wasted_steps == 0
    assert server.freeze_faults == 0


def _settled_trees_equal(a, b, slot):
    """Two settled engines hold the same device: every K/V leaf
    bit-equal, and ``slot``'s row of ``SlotCache.state`` (token,
    position, budget, sampling knobs, rng words) too."""
    assert not a._inflight and not b._inflight
    for (path, x), y in zip(
            jax.tree_util.tree_flatten_with_path(a.slots.cache)[0],
            jax.tree_util.tree_leaves(b.slots.cache)):
        assert np.array_equal(np.array(x), np.array(y)), \
            jax.tree_util.keystr(path)
    assert np.array_equal(np.array(a.slots.state)[slot],
                          np.array(b.slots.state)[slot])


def _fresh_third(model, params, n_prompt, seed):
    """A seeded ``n_prompt``-token prompt whose third greedy token is
    one it has not emitted before (a stop token that strikes there),
    with its three tokens."""
    rng = np.random.default_rng(seed)
    for _ in range(64):
        prompt = rng.integers(1, 64, size=n_prompt).tolist()
        solo = _solo(model, params, prompt, 3)
        if solo[2] not in solo[:2]:
            return prompt, solo, rng
    pytest.fail("no seeded prompt emits a new token third")


@pytest.mark.parametrize("finish", ["eos", "budget"])
@pytest.mark.parametrize("paged", [True, False])
def test_frozen_tail_is_an_identity_write(tiny, paged, finish):
    """Rounds of depth 4 in which a greedy slot finishes at step 2 of
    the first, beside a sampled co-tenant, leave the device what
    rounds of depth 2 leave it, all but the first with that slot
    frozen or empty: every K/V leaf bit-equal (outside the two
    positions the slot wrote nothing landed), and the co-tenant's row
    of ``SlotCache.state`` — token, position, budget, rng words —
    bit-equal too. A frozen step is an empty slot's step, WITHIN a
    dispatch (the deep round's tail) and ACROSS dispatches (the round
    that was already queued behind the finish: the engine keeps two in
    flight), which is what the overlap leans on."""
    model, params = tiny
    # 8-token prompts under pages of 8: a slot's second page is taken
    # at the first enqueue and none is taken or freed after the
    # finish, so the pools compare whole
    prompt, solo, rng = _fresh_third(model, params, 8, 1)
    eos, budget = (solo[2], 10) if finish == "eos" else (-1, 3)
    co_prompt = rng.integers(1, 64, size=8).tolist()

    def drive(chunk_steps, steps, seed):
        srv = Server(model, params, batch_size=2, eos_id=eos,
                     min_bucket=8, chunk_steps=chunk_steps, paged=paged,
                     kv_page_size=8 if paged else 0)
        srv.submit(Request(prompt, budget, id="fin"))
        srv.submit(Request(co_prompt, 20, id="co", temperature=0.9,
                           top_k=8, seed=seed))
        done = [r for _ in range(steps) for r in srv.step()]
        # a step from idle enqueues two rounds, each later one one
        assert srv.dispatches == steps + 1 == srv.slots.rounds
        srv._settle(done)
        assert [(r.id, r.tokens) for r in done] == [("fin", solo)]
        slot = next(i for i, lv in enumerate(srv._live) if lv is not None)
        return srv, slot

    for seed in range(8):  # a co-tenant that draws no stop token
        deep, co = drive(4, 1, seed)
        if eos not in deep._live[co].generated:
            break
    else:
        pytest.fail("every seeded co-tenant drew the stop token")
    flat, co_flat = drive(2, 3, seed)
    # the tail of the round it finished in, and (a finish by EOS only:
    # one by length the host sees coming) the whole round queued behind
    queued = finish == "eos"
    assert deep.frozen_steps == 2 + 4 * queued
    assert flat.frozen_steps == 2 * queued
    assert deep.freeze_faults == 0 and deep.wasted_steps == 0
    assert co == co_flat
    assert deep._live[co].generated == flat._live[co].generated
    assert len(deep._live[co].generated) == 9
    if paged:
        assert (deep.slots.page_table[co]
                    == flat.slots.page_table[co]).all()
    _settled_trees_equal(deep, flat, co)


def _halves(srv, order):
    """Drive the chunk round's two halves by hand: ``"e"`` enqueues a
    round, ``"a"`` arrives on the oldest in flight."""
    done = []
    for half in order:
        if half == "e":
            with srv.phases.phase("decode.prepare"):
                srv._enqueue_round(*srv._plan_round())
        else:
            srv._arrive(done)
    return done


@pytest.mark.parametrize("depth", [1, 4])
@pytest.mark.parametrize("finish", ["eos", "budget"])
@pytest.mark.parametrize("paged", [True, False])
def test_round_over_an_unseen_finisher_is_the_serial_round(
        tiny, paged, finish, depth):
    """The device freezes a finisher the host has not seen: a round
    enqueued over a slot that finished at the LAST step of the round
    before, neither evicted nor patched (its state row still reads
    live), leaves the K/V tree and the sampled co-tenant's state row
    bit-equal to the serial order — read the tokens, evict, send the
    patch that empties the row, then the round. By budget (the device
    sees ``rem <= 0``) and by a stop token (it sees its own last
    token), paged and not, one step deep and four."""
    model, params = tiny
    # 8-token prompts, pages of 8: a slot's second page comes with the
    # first enqueue, in either order before the finish is seen, and
    # covers both rounds: the pools compare whole
    rng = np.random.default_rng(2)
    prompt, co_prompt = (rng.integers(1, 64, size=8).tolist()
                         for _ in range(2))

    def drive(order, eos, budget, fin_seed, co_seed):
        srv = Server(model, params, batch_size=2, eos_id=eos,
                     min_bucket=8, chunk_steps=depth, paged=paged,
                     kv_page_size=8 if paged else 0)
        for req in (Request(prompt, budget, id="fin", temperature=0.9,
                            top_k=8, seed=fin_seed),
                    Request(co_prompt, 20, id="co", temperature=0.9,
                            top_k=8, seed=co_seed)):
            with srv.phases.phase("admit.host"):
                assert srv._admit_one(req, [])
        return srv, _halves(srv, order)

    # the finisher samples (the tiny model's greedy stream repeats one
    # token): a seed under which its last token of the first round is
    # one it has not drawn before, so that as a stop token it strikes
    # exactly there
    for fin_seed in range(32):
        probe, _ = drive("ea", -1, 20, fin_seed, 0)
        toks = list(probe._live[0].generated)
        if toks[depth] not in toks[:depth]:
            break
    else:
        pytest.fail("no seeded finisher draws a new token there")
    eos, budget = (toks[depth], 20) if finish == "eos" \
        else (-1, depth + 1)
    for co_seed in range(8):  # a co-tenant that draws no stop token
        over, done = drive("eeaa", eos, budget, fin_seed, co_seed)
        if over._live[1] is not None:
            break
    else:
        pytest.fail("every seeded co-tenant drew the stop token")
    serial, done_serial = drive("eaea", eos, budget, fin_seed, co_seed)
    reason = "eos" if finish == "eos" else "length"
    for d in (done, done_serial):
        assert [(r.id, r.tokens, r.finish_reason) for r in d] \
            == [("fin", toks, reason)]
    assert over.rounds_overlapped == 1 and serial.rounds_overlapped == 0
    # overlapped, the second round met the finisher's row as the first
    # left it; serial, it met the patch that empties it
    assert over.slots.rows_patched == 2 and serial.slots.rows_patched == 3
    assert over.frozen_steps == (depth if finish == "eos" else 0)
    assert serial.frozen_steps == 0 == over.freeze_faults
    assert over._live[1].generated == serial._live[1].generated
    assert len(over._live[1].generated) == 1 + 2 * depth
    if paged:
        assert (over.slots.page_table[1]
                    == serial.slots.page_table[1]).all()
    _settled_trees_equal(over, serial, 1)


def test_admit_evict_scheduler_invariants(tiny):
    """More requests than slots: occupancy never exceeds batch_size, a
    slot never hosts two live requests, every request finishes exactly
    once, and the server drains clean."""
    model, params = tiny
    server = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8)
    n = 7
    for i in range(n):
        server.submit(Request([1 + i, 2, 3], max_new_tokens=3 + (i % 4),
                              id=i))
    seen = []
    while not server.done:
        assert server.n_active <= 2
        live = [x for x in server._live if x is not None]
        assert len({id(x.request) for x in live}) == len(live)
        assert server.n_active == len(live)
        for r in server.step():
            seen.append(r.id)
    assert sorted(seen) == list(range(n))
    assert server.n_active == 0 and server.n_pending == 0
    assert server.slots.free_slots() == [0, 1]
    assert server.steps > 0 and server.prefills == n
    # every slot's host state was cleared on evict
    assert not server.slots.active.any()
    assert (server.slots.lengths == 0).all()


def test_greedy_row_isolated_from_sampled_neighbors(tiny):
    """A greedy request's output must not depend on what it is
    co-scheduled with (per-slot rng + row-independent attention)."""
    model, params = tiny
    greedy = Request([1, 2, 3], max_new_tokens=6, id="g")
    alone = {r.id: r.tokens for r in Server(
        model, params, batch_size=2, min_bucket=8).run([greedy])}
    import copy

    mixed = {r.id: r.tokens for r in Server(
        model, params, batch_size=2, min_bucket=8).run([
            copy.deepcopy(greedy),
            Request([9, 9], max_new_tokens=6, temperature=0.9, top_k=8,
                    seed=5, id="s"),
        ])}
    assert mixed["g"] == alone["g"] == _solo(model, params, [1, 2, 3], 6)


def test_sampled_requests_reproducible_by_seed(tiny):
    model, params = tiny

    def reqs():
        return [Request([1, 2, 3], 5, temperature=0.9, top_k=8, seed=7,
                        id=0),
                Request([4, 5], 5, temperature=0.7, seed=3, id=1)]

    runs = []
    for _ in range(2):
        server = Server(model, params, batch_size=2, min_bucket=8)
        runs.append({r.id: r.tokens for r in server.run(reqs())})
    assert runs[0] == runs[1]
    # a different seed moves the draws (overwhelmingly likely)
    server = Server(model, params, batch_size=2, min_bucket=8)
    other = {r.id: r.tokens for r in server.run(
        [Request([1, 2, 3], 5, temperature=0.9, top_k=8, seed=8, id=0),
         Request([4, 5], 5, temperature=0.7, seed=3, id=1)])}
    assert other[1] == runs[0][1]  # untouched request unchanged
    assert all(0 <= t < 64 for t in other[0])


def test_submit_validation_and_budget_clamp(tiny):
    model, params = tiny  # max_seq_len = 32
    server = Server(model, params, batch_size=1, min_bucket=8)
    with pytest.raises(ValueError, match="empty"):
        server.submit(Request([], max_new_tokens=4))
    with pytest.raises(ValueError, match="no room"):
        server.submit(Request(list(range(32)), max_new_tokens=4))
    with pytest.raises(ValueError, match="max_new_tokens"):
        server.submit(Request([1, 2], max_new_tokens=0))
    # a 30-token prompt leaves room for 2: budget of 10 clamps to 2
    server.submit(Request(list(range(1, 31)), max_new_tokens=10, id="c"))
    res = {r.id: r for r in server.run()}
    assert len(res["c"].tokens) == 2
    assert res["c"].finish_reason == "length"


def test_serve_per_slot_matches_solo_with_kv_int8(tiny):
    """Per-slot decode writes quant scales by scatter (the scalar path
    uses dynamic_update_slice): same values, same outputs — greedy
    through the int8 KV cache must equal the solo int8-KV decode."""
    import dataclasses

    model, params = tiny
    qmodel = Transformer(dataclasses.replace(model.cfg,
                                             kv_cache_quant=True))
    prompts = [[1, 2, 3], [5, 9, 11, 8]]
    server = Server(qmodel, params, batch_size=2, min_bucket=8)
    res = {r.id: r for r in server.run(
        Request(p, max_new_tokens=5) for p in prompts)}
    for i, p in enumerate(prompts):
        assert res[i].tokens == _solo(qmodel, params, p, 5), p


def test_serve_flash_decode_backend(tiny):
    """The serving step through the pallas flash-decode kernel
    (interpreted on CPU): per-slot lengths feed the kernel's [B] length
    vector; outputs match the einsum serve path."""
    import dataclasses

    model, params = tiny
    fmodel = Transformer(dataclasses.replace(model.cfg,
                                             decode_attention="flash"))
    prompts = [[1, 2, 3], [5, 9]]
    ref = {r.id: r.tokens for r in Server(
        model, params, batch_size=2, min_bucket=8).run(
        Request(p, max_new_tokens=4) for p in prompts)}
    got = {r.id: r.tokens for r in Server(
        fmodel, params, batch_size=2, min_bucket=8).run(
        Request(p, max_new_tokens=4) for p in prompts)}
    assert got == ref


@pytest.mark.parametrize("chunk_steps", [1, 2])
def test_continuous_beats_fixed_on_decode_steps(tiny, chunk_steps):
    """The scheduling claim in its launch-overhead-free form: on a
    mixed-budget workload the continuous scheduler executes strictly
    fewer batched decode steps than fixed batching's
    sum-of-batch-maxima (tokens per second is read on the chip, by
    ``benchmarks/run.py``; step counts are deterministic). With two
    rounds in the device's queue a freed slot stands empty for the
    round already queued, a whole chunk: 23 and 24 steps here against
    fixed batching's 29, and at depth 4, where the serial order took
    22, it is 30 (ROADMAP S2 (c))."""
    model, params = tiny
    budgets = [3, 14, 5, 9, 4, 12, 6, 15]
    batch = 4
    fixed_steps = sum(max(budgets[i:i + batch])
                      for i in range(0, len(budgets), batch))
    server = Server(model, params, batch_size=batch, eos_id=-1,
                    min_bucket=8, chunk_steps=chunk_steps)
    n_done = sum(1 for _ in server.run(
        Request([1 + i, 2, 3], max_new_tokens=b, id=i)
        for i, b in enumerate(budgets)))
    assert n_done == len(budgets)
    assert server.steps < fixed_steps, (server.steps, fixed_steps)


def test_slotcache_admit_evict_reset(tiny):
    model, params = tiny
    slots = SlotCache(model, params, 3)
    assert slots.free_slots() == [0, 1, 2]
    assert list(slots.positions()) == [-1, -1, -1]
    slots.admit(1, length=4, last_token=7, temperature=0.5, top_k=3,
                rng_key=jax.random.PRNGKey(1))
    assert slots.free_slots() == [0, 2]
    assert slots.n_active == 1
    assert list(slots.positions()) == [-1, 4, -1]
    with pytest.raises(ValueError, match="occupied"):
        slots.admit(1, length=2, last_token=0, temperature=0.0, top_k=0,
                    rng_key=jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="length"):
        slots.admit(0, length=0, last_token=0, temperature=0.0, top_k=0,
                    rng_key=jax.random.PRNGKey(0))
    slots.evict(1)
    assert slots.free_slots() == [0, 1, 2]
    slots.admit(0, length=2, last_token=1, temperature=0.0, top_k=0,
                rng_key=jax.random.PRNGKey(0))
    slots.reset()
    assert slots.n_active == 0 and not slots.active.any()


def test_slotcache_row_copy_isolated(tiny):
    """admit(row_cache=...) writes exactly one slot's row: other slots'
    cache content is untouched (the standalone copy path the engine
    fuses into its prefill dispatch)."""
    from tony_tpu.models import init_cache

    model, params = tiny
    slots = SlotCache(model, params, 2)
    row = init_cache(model, params, 1)
    row = jax.tree_util.tree_map(
        lambda x: jnp.full_like(x, 3) if x.ndim >= 3 else x, row)
    held = jax.tree_util.tree_leaves(slots.cache)
    # copied to numpy BEFORE the writer runs: it donates the tree it is
    # given (np.asarray would be a view that pins the buffer on the CPU
    # and so costs the writer its donation)
    before = [np.array(leaf) for leaf in held]
    slots.admit(1, length=1, last_token=0, temperature=0.0, top_k=0,
                rng_key=jax.random.PRNGKey(0), row_cache=row)
    assert all(leaf.is_deleted() for leaf in held)
    assert (slots.tree_donated, slots.tree_kept) == (1, 0)
    for old, new in zip(before, jax.tree_util.tree_leaves(slots.cache)):
        if new.ndim >= 4:  # KV buffers [b, S, kvh, dh]
            np.testing.assert_array_equal(np.asarray(new[0]), old[0])
            assert (np.asarray(new[1]) == 3).all()


@pytest.mark.parametrize("scan_layers,kv_int8", [
    # the satellite case: stacked [n_layers, ...] leaves AND int8
    # scale leaves together; the plain layout rides the slow tier
    # (every serve test exercises it implicitly through admit/evict)
    (True, True),
    pytest.param(False, False, marks=pytest.mark.slow)])
def test_slot_row_write_read_roundtrip(scan_layers, kv_int8):
    """read_slot_row is the EXACT inverse of write_slot_row for every
    batched leaf — including scan_layers' stacked [n_layers, ...] KV
    buffers (batch is 4th-from-last, NOT axis 0) and int8-KV scale
    leaves (batch 3rd-from-last). The prefix store's donation path
    (engine._donate -> read_slot_row -> later write via
    _prefill_admit/_hit_admit) depends on this bit-for-bit."""
    import dataclasses

    from tony_tpu.models import init_cache
    from tony_tpu.serve import cache_batch_axis, read_slot_row, \
        write_slot_row

    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=16,
                            dtype=jnp.float32,
                            attention_backend="reference")
    cfg = dataclasses.replace(cfg, scan_layers=scan_layers,
                              kv_cache_quant=kv_int8)
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    cache = init_cache(model, params, 3)
    # fill every leaf with distinct values so a wrong-axis slice would
    # come back provably different
    rng = np.random.default_rng(0)

    def randomize(leaf):
        vals = rng.integers(-100, 100, size=leaf.shape)
        return jnp.asarray(vals, leaf.dtype)

    cache = jax.tree_util.tree_map(randomize, cache)
    row = jax.tree_util.tree_map(
        lambda leaf: randomize(leaf),
        init_cache(model, params, 1))
    slot = 1
    written = write_slot_row(cache, row, slot)
    back = read_slot_row(written, slot)
    leaves_c = jax.tree_util.tree_flatten_with_path(cache)[0]
    leaves_r = jax.tree_util.tree_leaves(row)
    leaves_w = jax.tree_util.tree_leaves(written)
    leaves_b = jax.tree_util.tree_leaves(back)
    saw_scale = saw_stacked = False
    for (path, old), r, w, b in zip(leaves_c, leaves_r, leaves_w,
                                    leaves_b):
        ax = cache_batch_axis(path, old)
        name = str(path[-1].key if hasattr(path[-1], "key")
                   else path[-1])
        if ax is None:
            # shared counters pass through unchanged in both directions
            np.testing.assert_array_equal(np.asarray(w), np.asarray(old))
            np.testing.assert_array_equal(np.asarray(b), np.asarray(old))
            continue
        saw_scale |= name.endswith("_scale")
        saw_stacked |= scan_layers and old.ndim >= 5
        # write-then-read round-trips the row exactly...
        np.testing.assert_array_equal(np.asarray(b), np.asarray(r))
        # ...and the OTHER slots' content is untouched
        others = [i for i in range(3) if i != slot]
        np.testing.assert_array_equal(
            np.asarray(jnp.take(w, np.asarray(others), axis=ax)),
            np.asarray(jnp.take(old, np.asarray(others), axis=ax)))
    assert saw_scale == kv_int8
    if scan_layers:
        assert saw_stacked


def test_bucket_len():
    assert bucket_len(3, 2048) == 16
    assert bucket_len(16, 2048) == 16
    assert bucket_len(17, 2048) == 32
    assert bucket_len(1500, 2048) == 2048
    assert bucket_len(5, 8, minimum=4) == 8


def test_results_stream_in_finish_order(tiny):
    """Short requests surface before long ones submitted earlier — the
    point of iteration-level scheduling."""
    model, params = tiny
    server = Server(model, params, batch_size=2, eos_id=-1, min_bucket=8,
                    chunk_steps=1)
    order = [r.id for r in server.run([
        Request([1, 2, 3], max_new_tokens=12, id="long"),
        Request([5, 9], max_new_tokens=2, id="short"),
    ])]
    assert order == ["short", "long"]


@pytest.mark.slow  # heavyweight; tier-1 runs -m 'not slow'
def test_serve_cli_jsonl(tiny, tmp_path):
    """generate --serve end-to-end over a local HF checkpoint: JSONL
    in -> JSONL out, greedy parity with HF generate per request."""
    import json
    import os
    import subprocess
    import sys

    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    config = transformers.LlamaConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=32, tie_word_embeddings=True)
    torch.manual_seed(0)
    hf = transformers.LlamaForCausalLM(config).eval()
    mdir = tmp_path / "ckpt"
    hf.save_pretrained(str(mdir))
    reqs = [("a", [1, 2, 3], 4), ("b", [9, 8], 6), ("c", [5, 6, 7, 8], 3)]
    stdin = "\n".join(json.dumps({"id": rid, "token_ids": ids,
                                  "max_new_tokens": n})
                      for rid, ids, n in reqs)
    proc = subprocess.run(
        [sys.executable, "-m", "tony_tpu.cli.generate", "--model",
         str(mdir), "--serve", "--serve-batch", "2", "--eos-id", "63"],
        input=stdin, capture_output=True, text=True, timeout=240,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": os.path.dirname(os.path.dirname(
                 os.path.abspath(__file__)))})
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = [json.loads(ln) for ln in proc.stdout.strip().splitlines()]
    got = {ln["id"]: ln for ln in lines}
    assert set(got) == {"a", "b", "c"}
    for rid, ids, n in reqs:
        with torch.no_grad():
            ref = hf.generate(torch.tensor([ids]), max_new_tokens=n,
                              do_sample=False, pad_token_id=0,
                              eos_token_id=63)
        assert got[rid]["token_ids"] == ref[0].tolist(), rid
        assert got[rid]["finish_reason"] in ("eos", "length")
