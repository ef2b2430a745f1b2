"""The decode round's carry lives on the device (``SlotCache.state``).

Last token, position, budget, sampling knobs and rng key stay resident
between chunk rounds; the host sends one packed patch for the rows that
admission, eviction or a host-fed round changed, sends nothing in the
other rounds, and copies back the tokens only. CPU, tiny model: counts
and tokens, no wall clock.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.models import Transformer, TransformerConfig
from tony_tpu.serve import Request, Server
from tony_tpu.serve import engine as E
from tony_tpu.serve.slots import unpack_state


@pytest.fixture(scope="module")
def tiny():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=64, max_seq_len=64,
                            dtype=jnp.float32,
                            attention_backend="reference")
    model = Transformer(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _prompt(seed, n=12):
    return np.random.default_rng(seed).integers(1, 64, size=n).tolist()


def _run(srv, requests) -> dict:
    for r in requests:
        srv.submit(r)
    return {res.id: res.tokens for res in srv.run()}


def _device_rows(srv) -> dict:
    names = ("tok", "pos", "rem", "top_k", "temp", "rng")
    return {n: np.asarray(x)
            for n, x in zip(names, unpack_state(srv.slots.state))}


# ------------------------------------------- (1) the state follows the host


@pytest.mark.parametrize("chunk_steps", [1, 2, 4, 8])
@pytest.mark.parametrize("paged", [True, False])
def test_clean_rows_equal_the_mirrors_after_every_round(
        tiny, paged, chunk_steps):
    """After every step, for every live row the host has not touched
    since, the device's last token, position and budget are where the
    host planned them: the mirrors' plus the rounds in flight — staggered budgets and a late arrival, so rows are
    admitted and evicted around the ones compared; at every depth the
    scheduler compiles up to ``Server``'s default of 8."""
    model, params = tiny
    srv = Server(model, params, batch_size=3, paged=paged,
                 chunk_steps=chunk_steps,
                 kv_page_size=8 if paged else 0)
    for i, budget in enumerate([9, 48, 30]):
        srv.submit(Request(_prompt(i), budget, id=i,
                           temperature=0.7 * (i == 1), top_k=5, seed=3))
    compared = 0
    for it in range(80):
        if it == 3:
            srv.submit(Request(_prompt(9), 12, id="late"))
        srv.step()
        # the mirrors lag the device by the rounds in flight: the
        # device stands where the host PLANNED it (``_budgets``), the
        # mirror plus the depth of every round the row still rides,
        # and its last token is the newest of those rounds' last
        s = srv.slots
        dev = _device_rows(srv)
        left = srv._budgets()
        for slot in np.flatnonzero(s.active & ~s.dirty):
            if left[slot] <= 0:
                continue    # ends under the rounds in flight: frozen
            riding = [r for r in srv._inflight if slot in r.riders]
            ahead = sum(r.k for r in riding)
            tok = int(np.asarray(riding[-1].toks)[slot, -1]) if riding \
                else s.last_token[slot]
            assert dev["tok"][slot] == tok
            assert dev["pos"][slot] == s.positions()[slot] + ahead
            assert dev["top_k"][slot] == s.top_k[slot]
            assert dev["temp"][slot] == s.temperature[slot]
            assert dev["rem"][slot] == left[slot]
            compared += 1
        if srv.done:
            break
    assert srv.done and compared >= 8
    c = srv.counters()
    assert c["decode_rounds"] == c["dispatches"] > 0
    assert c["kv_tree_kept"] == 0


# --------------------------------------------- (2) a clean round sends nothing


def test_rounds_without_admission_send_no_state_and_no_table(tiny):
    """One admission, then N rounds: the first sends the admitted row
    and the table, the other N - 1 send neither; the eviction's row
    goes with the first round of the next request."""
    model, params = tiny
    # page 16, prompt 12 + 1 sampled by the prefill: positions 12..19
    # stay inside page 1's bucket of columns for the 4 rounds counted
    srv = Server(model, params, batch_size=2, chunk_steps=1,
                 kv_page_size=16)
    n = 4
    srv.submit(Request(_prompt(0), n + 1, id="a"))
    while not srv.done:
        srv.step()
    c = srv.counters()
    assert c["decode_rounds"] == n
    assert c["decode_rounds_clean"] == n - 1
    assert c["decode_rows_patched"] == 1   # the admission
    assert c["decode_table_sends"] == 1
    assert c["decode_rng_pulls"] == 0
    srv.submit(Request(_prompt(1), n + 1, id="b"))
    while not srv.done:
        srv.step()
    c = srv.counters()
    assert c["decode_rounds"] == 2 * n
    assert c["decode_rounds_clean"] == 2 * (n - 1)
    # a's eviction and b's admission (the same slot or two: the dirty
    # flag is a row's, so one slot used twice is one row sent)
    assert c["decode_rows_patched"] in (2, 3)
    assert c["decode_rng_pulls"] == 0  # greedy never pulls


def test_counters_reach_stats_engine(tiny):
    from tony_tpu.gateway import Gateway, GenRequest

    model, params = tiny
    gw = Gateway([Server(model, params, batch_size=2)]).start()
    try:
        gw.submit(GenRequest(_prompt(0), max_new_tokens=6)).result(
            timeout=120)
        eng = gw.snapshot()["engine"]
    finally:
        gw.drain(timeout=60)
    assert eng["decode_rounds"] >= 1
    assert eng["decode_rounds_clean"] == eng["decode_rounds"] - 1
    assert eng["decode_rows_patched"] == 1
    assert eng["decode_table_sends"] >= 1
    assert eng["decode_rng_pulls"] == 0


# ------------------------------------- (3) a sampled stream is its own alone


@pytest.mark.parametrize("chunk_steps", [1, 4])
@pytest.mark.parametrize("paged", [True, False])
def test_sampled_stream_is_the_same_alone_and_among_cotenants(
        tiny, paged, chunk_steps):
    model, params = tiny
    kw = dict(batch_size=3, paged=paged, chunk_steps=chunk_steps)
    sampled = dict(temperature=0.9, top_k=8, seed=11)
    alone = _run(Server(model, params, **kw),
                 [Request(_prompt(0), 24, id="s", **sampled)])["s"]
    srv = Server(model, params, **kw)
    srv.submit(Request(_prompt(1), 4, id="g0"))
    srv.submit(Request(_prompt(0), 24, id="s", **sampled))
    got = {}
    for it in range(80):
        if it in (3, 7, 12):  # co-tenants come and go around it
            srv.submit(Request(_prompt(20 + it), 3 + it % 4, id=it,
                               temperature=0.5 * (it == 7), seed=it))
        for res in srv.step():
            got[res.id] = res.tokens
        if srv.done:
            break
    assert got["s"] == alone
    # the device kept the key: nobody asked the host for it
    assert srv.counters()["decode_rng_pulls"] == 0


# ----------------------------------- (4) a snapshot carries the device's key


@pytest.mark.parametrize("after", [1, 5])
def test_snapshot_mid_stream_carries_the_device_key(tiny, after):
    model, params = tiny
    kw = dict(batch_size=2, paged=True, kv_page_size=8, chunk_steps=1)
    sampled = dict(temperature=0.8, top_k=8, seed=7)
    prompt, budget = _prompt(3, 13), 30
    whole = _run(Server(model, params, **kw),
                 [Request(prompt, budget, id="c", **sampled)])["c"]
    src = Server(model, params, **kw)
    src.submit(Request(prompt, budget, id="m", **sampled))
    while src.counters()["decode_rounds"] < after:
        src.step()
    host_copy = src.slots._rng.copy()
    snap = src.extract_session("m", wire=True)
    assert src.counters()["decode_rng_pulls"] == 1
    # the key that travels is the device's, not the admission's
    assert not (snap.rng == host_copy[0]).all()
    assert whole[:len(snap.generated)] == snap.generated
    dst = Server(model, params, **kw)
    got = _run(dst, [Request(prompt, budget, id="m", migrate=snap,
                             **sampled)])["m"]
    assert got == whole


# ------------------------------- (5) chunk and verify rounds hand over whole


@pytest.mark.parametrize("chunk_steps", [1, 2])
@pytest.mark.parametrize("paged", [True, False])
def test_chunk_and_verify_rounds_interleaved_give_the_plain_stream(
        tiny, paged, chunk_steps):
    """A repetitive greedy prompt drafts (verify rounds), a random
    greedy one and a sampled one ride along: rounds of both kinds
    alternate, each handing the per-slot values to the other, and every
    stream is the one speculation-off gives. Depth 1 is what
    ``--speculate-k`` meets under the gateway's ``--chunk-steps 1``."""
    model, params = tiny

    def drive(srv) -> tuple:
        srv.submit(Request([5, 6, 7, 8] * 5, 6, id="rep"))
        srv.submit(Request(_prompt(2), 12, id="rand"))
        srv.submit(Request(_prompt(4), 45, id="samp", temperature=0.9,
                           top_k=6, seed=5))
        got, kinds = {}, ""
        for _ in range(80):
            if "c" in kinds and "rep2" not in got:
                # a drafter again, once plain chunk rounds have run
                # (the sampled row never drafts)
                srv.submit(Request([9, 10, 11] * 6, 6, id="rep2"))
                got["rep2"] = None
            before = srv.spec_rounds
            for res in srv.step():
                got[res.id] = res.tokens
            kind = "v" if srv.spec_rounds > before else "c"
            kinds += kind if kinds[-1:] != kind else ""
            if srv.done:
                break
        return got, kinds

    kw = dict(batch_size=3, paged=paged, chunk_steps=chunk_steps)
    plain, _ = drive(Server(model, params, **kw))
    srv = Server(model, params, speculate_k=3, **kw)
    got, kinds = drive(srv)
    assert got == plain and len(plain) == 4
    assert "cvc" in kinds, kinds  # both hand-overs happened
    c = srv.counters()
    # the verify round that follows chunk rounds reads the sampled
    # row's key back, and after each verify round the live rows go to
    # the device again with the next chunk round
    assert c["decode_rng_pulls"] >= 1
    assert c["decode_rows_patched"] > 4


# -------------------------------------- (6) one program, patched or clean


@pytest.mark.parametrize("placement", ["default", "committed", "mesh2"])
def test_clean_and_patched_rounds_share_one_executable(tiny, placement):
    """The first round (the first state, a patch), a clean round (the
    resident all-clear patch, a successor state) and a later patched
    round meet ONE executable per ``(n_steps, columns)`` — with the
    parameters wherever jax put them, committed to a device, or under
    a mesh, where the state is pinned replicated."""
    model, params = tiny
    mesh = None
    if placement == "committed":
        params = jax.device_put(params, jax.devices()[0])
    elif placement == "mesh2":
        from tony_tpu.parallel.mesh import MeshSpec, make_mesh

        mesh = make_mesh(MeshSpec(data=1, tensor=2),
                         devices=jax.devices()[:2])
    # one page of 32 holds every position: one bucket of view columns
    srv = Server(model, params, batch_size=2, chunk_steps=1,
                 kv_page_size=32, mesh=mesh)
    before = E._decode_chunk._cache_size()
    srv.submit(Request(_prompt(0), 7, id="a"))
    sizes = []
    for it in range(12):
        if it == 3:
            srv.submit(Request(_prompt(1), 3, id="b"))
        srv.step()
        sizes.append(E._decode_chunk._cache_size())
        if srv.done:
            break
    c = srv.counters()
    assert c["decode_rounds_clean"] >= 2 and c["decode_rows_patched"] >= 2
    # every round stayed within one view bucket: one program
    assert sizes[-1] - before == 1, sizes
