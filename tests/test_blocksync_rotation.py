"""Block-sync across validator-set changes: the reactor PLANS a run before
it verifies it (blocksync/reactor.py `_verify_and_apply`).

After height h the state holds the validator sets of h+1 and h+2, and every
fetched header names its own set, so each commit goes to
`verify_commit_range` beside the set its header names — as many blocks as
name one of those two sets in ONE call — the run is cut where a header names
a third set, and the rest is planned again from the state the apply
produced. No commit is verified against a set the state did not derive, none
twice, and `_apply_sequential` (one commit at a time) is left to what it is
for: a planned call that FAILED against its true sets.

Chains are built through the real executor with kvstore `val:` transactions
(effective two heights later); the reactor is driven one hand-made run at a
time (deterministic run boundaries) with the process hub running, so "asked
of the hub exactly once" is read from the hub's own counters; one case goes
through the reactor's own loop and peers. The plain reference for a
changing set is `benchmark/reference_churn.py`."""

import asyncio
import dataclasses

import pytest

from benchmark import fixtures_churn
from benchmark import reference as ref
from benchmark import reference_churn as refc
from tendermint_tpu import testing as tt
from tendermint_tpu.blocksync import BLOCKSYNC_CHANNEL
from tendermint_tpu.blocksync import messages as bsm
from tendermint_tpu.blocksync import reactor as reactor_mod
from tendermint_tpu.blocksync.reactor import BlockSyncReactor
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.p2p.peermanager import PeerStatus, PeerUpdate
from tendermint_tpu.p2p.router import Channel
from tendermint_tpu.p2p.types import Envelope
from tendermint_tpu.state.validation import median_time
from tendermint_tpu.testing import det_priv_keys
from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

from benchmark.fixtures import fresh_node

CHAIN = "rotation-chain"
N_VALS = 5
N_BLOCKS = 26
POWER = 10


def _val_tx(key, power):
    return b"val:" + key.pub_key().bytes().hex().encode() + b"!%d" % power


KEYS = det_priv_keys(N_VALS + 2, seed=b"rot")
#: the genesis validators in SET order (equal powers: by address), so that a
#: case can say who moves: 0 sits first, 4 last; 5 and 6 are fresh keys
KEYS = sorted(KEYS[:N_VALS], key=lambda k: k.pub_key().address()) + KEYS[N_VALS:]

#: case -> {height that carries the change: [(validator, new power)]}
CASES = {
    "join": {10: [(5, POWER)]},
    "leave": {10: [(4, 0)]},
    "power_change": {10: [(3, POWER + 1)]},
    "swap": {10: [(4, 0), (5, POWER)]},
    "two_changes_two_heights_apart": {9: [(2, POWER + 1)], 11: [(5, POWER)]},
    # runs of 12: heights 1..12 and 13..24 — new sets take effect at the
    # second run's first block (13) and at its last (24)
    "change_at_the_run_s_first_and_last_block": {11: [(1, POWER + 1)], 22: [(6, POWER)]},
    # the same validator up and down again: the THIRD set hashes to the first
    "there_and_back": {8: [(0, POWER + 1)], 12: [(0, POWER)]},
    "static": {},
}


def _run(chain, first, last):
    """Blocks first..last as the pool hands them over: the last one only
    vouches for its predecessor."""
    return [(chain.store.load_block(h), f"peer{h % 2}") for h in range(first, last + 1)]


async def _build(schedule, n_blocks=N_BLOCKS) -> fixtures_churn.ChurnChain:
    keys = KEYS
    genesis = GenesisDoc(
        chain_id=CHAIN, initial_height=1, genesis_time_ns=1_700_000_000_000_000_000,
        validators=[GenesisValidator(k.pub_key(), POWER, f"v{i}")
                    for i, k in enumerate(keys[:N_VALS])],
    )
    by_addr = {k.pub_key().address(): k for k in keys}
    app, conns, store, state, ex = await fresh_node(genesis)
    txs_at, app_hash_at, set_hash_at, set_objs = {}, {}, {}, {}
    commit = None
    for h in range(1, n_blocks + 1):
        txs = tuple([b"k%d=v%d" % (h, h)]
                    + [_val_tx(keys[i], p) for i, p in schedule.get(h, ())])
        time_ns = (state.last_block_time_ns if h == 1
                   else median_time(commit, state.last_validators))
        set_hash_at[h] = state.validators.hash()
        set_objs.setdefault(set_hash_at[h], state.validators)
        block = state.make_block(h, txs, commit, (),
                                 state.validators.get_proposer().address, time_ns)
        parts = block.make_part_set()
        bid = block.block_id(parts.header)
        state, _ = await ex.apply_block(state, bid, block, commit_verified=True)
        txs_at[h], app_hash_at[h] = txs, app.app_hash
        commit = tt.make_commit(CHAIN, h, 0, bid, state.last_validators, by_addr,
                                timestamp_ns=block.header.time_ns + 1)
        store.save_block(block, parts, commit)
    await conns.stop()
    sets = refc.derive_sets([(k.pub_key().bytes(), POWER) for k in keys[:N_VALS]],
                            txs_at, n_blocks)
    return fixtures_churn.ChurnChain(
        chain_id=CHAIN, genesis=genesis, vals=set_objs[set_hash_at[1]], store=store,
        n_blocks=n_blocks, app_hash_at=app_hash_at, txs_at=txs_at, wire={}, sets=sets,
        changes={}, keys=by_addr, set_hash_at=set_hash_at, set_objs=set_objs)


class Node:
    """A fresh node's reactor, not started: the test hands it runs."""

    def __init__(self):
        self.calls = []  # [(height, hash of the set the entry carried)] a range call
        self.singles = []  # heights verified one commit at a time

    async def start(self, genesis, window=64):
        self.app, self.conns, self.bstore, state, self.ex = await fresh_node(genesis)
        self.ch = Channel(BLOCKSYNC_CHANNEL, "bs", 5, bsm.encode_message, bsm.decode_message)
        self.peer_q = asyncio.Queue()
        self.reactor = BlockSyncReactor(state, self.ex, self.bstore, self.ch, self.peer_q,
                                        window=window, active=True)
        return self

    def punished(self):
        out = []
        while not self.ch.err_q.empty():
            out.append(self.ch.err_q.get_nowait())
        return out


@pytest.fixture
def spy(monkeypatch):
    """The reactor's two verification entries, recorded; and the process hub,
    so that what was ASKED is the hub's own count."""
    node = Node()
    many, one = reactor_mod.verify_commit_range, reactor_mod.verify_commit_light

    def spy_many(chain_id, entries, **kw):
        node.calls.append([(h, vals.hash()) for vals, _bid, h, _c in entries])
        return many(chain_id, entries, **kw)

    def spy_one(chain_id, vals, block_id, height, commit, **kw):
        node.singles.append(height)
        return one(chain_id, vals, block_id, height, commit, **kw)

    monkeypatch.setattr(reactor_mod, "verify_commit_range", spy_many)
    monkeypatch.setattr(reactor_mod, "verify_commit_light", spy_one)
    node.hub = vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=8192)
    yield node
    vh.release_hub()


def _asked(hub, before):
    s = hub.stats()
    return {k: s[k] - before.get(k, 0) for k in ("submitted", "cache_hits", "coalesced")}


@pytest.mark.asyncio
@pytest.mark.parametrize("case", sorted(CASES))
async def test_a_run_is_planned_at_the_sets_the_state_knows(spy, case):
    chain = await _build(CASES[case])
    # the reference's derivation is the program's, at every height
    assert [chain.sets[h].hash for h in range(1, N_BLOCKS + 1)] == [
        chain.set_hash_at[h] for h in range(1, N_BLOCKS + 1)]
    node = await spy.start(chain.genesis)
    before = dict(spy.hub.stats())
    runs = [(1, 12), (13, 24)]
    for first, last in runs:
        await node.reactor._verify_and_apply(_run(chain, first, last + 1))
    await node.conns.stop()

    # the whole of both runs applied, nobody punished, nothing one at a time
    assert node.bstore.height() == 24 and node.reactor.state.last_block_height == 24
    assert node.punished() == []
    assert node.singles == [] and node.reactor.metrics["sequential_blocks"] == 0
    assert node.app.app_hash == chain.app_hash_at[24] == refc.kv_state_hash(
        [tx for h in range(1, 25) for tx in chain.txs_at[h]])
    # each sub-range as planned: the calls the reference's rule gives, every
    # entry beside the set of its own height
    want = [p for first, last in runs
            for p in refc.expected_plans(chain.sets, first, last - first + 1)]
    assert [(c[0][0], len(c)) for c in node.calls] == want
    assert all(vh_ == chain.sets[h].hash for c in node.calls for h, vh_ in c)
    assert max(len({s for _h, s in c}) for c in node.calls) <= 2
    m = node.reactor.metrics
    assert m["plans"] == m["ranges"] == len(want)
    assert m["cuts"] == len(want) - len(runs)
    if case == "static":
        assert want == [(1, 12), (13, 12)]
    # every commit asked of the hub exactly once: what the > 2/3 rule needs
    # under each height's own set, no verdict answered from the cache
    needed = sum(ref.commit_verdict(chain.commit_data(h))[1] for h in range(1, 25))
    asked = _asked(spy.hub, before)
    assert asked == {"submitted": needed, "cache_hits": 0, "coalesced": 0}
    # block 13 was applied under a proof made by the first run's last entry
    assert node.reactor._commit_proofs == {24: chain.sets[24].hash}
    # the store holds each height's set and the headers name them
    for h in range(1, 25):
        assert node.ex.state_store.load_validators(h).hash() == chain.sets[h].hash
        hdr = node.bstore.load_block_meta(h).header
        assert (hdr.validators_hash, hdr.next_validators_hash) == (
            chain.sets[h].hash, chain.sets[h + 1].hash)


@pytest.mark.asyncio
@pytest.mark.parametrize("kind", ["stale_set", "bitflip"])
async def test_a_commit_that_fails_its_own_height_s_set_is_refused_there(spy, kind):
    """Byzantine: the commit for the first height of a new set, signed by
    every validator of the set of the height BEFORE (valid under the stale
    set, which the reference confirms, and under no other) — or an honest
    one with a signature bit flipped. Blocks before it are applied, the
    provider pair is punished, nothing at or after it is applied."""
    chain = await _build(CASES["power_change"])
    bad_h = 12  # the change in block 10 makes the set of 12
    assert chain.sets[bad_h].hash != chain.sets[bad_h - 1].hash
    honest = chain.store.load_block_commit(bad_h)
    if kind == "stale_set":
        forged = chain.stale_commit(bad_h)
        assert fixtures_churn.stale_commit_is_telling(chain, bad_h)
    else:
        from benchmark.fixtures import corrupt_commit

        forged = corrupt_commit(honest, 1)
    assert ref.commit_verdict(chain.commit_data(bad_h, forged))[0] is False
    run = _run(chain, 1, 20)
    nxt, provider = run[bad_h]  # block 13 carries the commit for 12
    run[bad_h] = (dataclasses.replace(nxt, last_commit=forged), provider)

    node = await spy.start(chain.genesis)
    await node.reactor._verify_and_apply(run)
    await node.conns.stop()
    assert node.bstore.height() == bad_h - 1
    assert node.reactor.state.last_block_height == bad_h - 1
    punished = node.punished()
    assert sorted(e.node_id for e in punished) == sorted({run[bad_h - 1][1], provider})
    assert all("invalid" in e.err or "bad" in e.err for e in punished)
    # the planned call held heights 12.. to the NEW set and failed there; the
    # fallback went one commit at a time up to the bad one
    assert (12, chain.sets[12].hash) in node.calls[-1]
    assert node.singles == list(range(node.calls[-1][0][0], bad_h + 1))
    assert node.reactor.metrics["sequential_blocks"] == bad_h - node.calls[-1][0][0]
    assert all(h < bad_h for h in node.reactor._commit_proofs)


@pytest.mark.asyncio
async def test_a_header_that_names_a_set_the_state_contradicts_is_refused(spy):
    """A block whose header names a third set cannot be planned; it is held
    to the state's own set one commit at a time, fails (the commit vouches
    for the honest block), and its providers are punished."""
    chain = await _build(CASES["power_change"])
    run = _run(chain, 1, 9)
    block, provider = run[4]
    lying = dataclasses.replace(
        block, header=dataclasses.replace(block.header, validators_hash=b"\x07" * 32))
    run[4] = (lying, provider)
    node = await spy.start(chain.genesis)
    await node.reactor._verify_and_apply(run)
    await node.conns.stop()
    assert node.bstore.height() == 4 and node.singles == [5]
    assert [(c[0][0], len(c)) for c in node.calls] == [(1, 4)]
    assert {e.node_id for e in node.punished()} == {run[4][1], run[5][1]}
    m = node.reactor.metrics
    assert (m["plans"], m["cuts"], m["ranges"], m["sequential_blocks"]) == (2, 2, 1, 0)


@pytest.mark.asyncio
@pytest.mark.parametrize("seed", [3000003501, 3000003502, 7])
async def test_the_system_against_the_reference_on_seeded_churn_chains(spy, seed):
    """`benchmark/fixtures_churn` (the cell's own chain: a change every
    `period` heights, every fourth a swap) at 7 validators: the set hash of
    every height, the verdict on every commit, and the app hash — the
    program's against `reference_churn`'s."""
    chain = await fixtures_churn.churn_chain(seed, "rot", 50, 7, 10, 2, period=4, swap_every=4)
    assert sorted(set(chain.changes.values())) == ["power", "swap"]
    assert [chain.sets[h].hash for h in range(1, 51)] == [chain.set_hash_at[h]
                                                         for h in range(1, 51)]
    assert all(len(chain.sets[h].pubkeys) == 7 for h in range(1, 53))
    node = await spy.start(chain.genesis)
    before = dict(spy.hub.stats())
    run = [(chain.store.load_block(h), "peer0") for h in range(1, 51)]
    await node.reactor._verify_and_apply(run)
    await node.conns.stop()
    assert node.bstore.height() == 49 and node.punished() == [] and node.singles == []
    verdicts = [ref.commit_verdict(chain.commit_data(h)) for h in range(1, 50)]
    assert all(v[0] for v in verdicts)
    assert _asked(spy.hub, before) == {
        "submitted": sum(v[1] for v in verdicts), "cache_hits": 0, "coalesced": 0}
    assert [(c[0][0], len(c)) for c in node.calls] == refc.expected_plans(chain.sets, 1, 49)
    assert node.app.app_hash == chain.app_hash_at[49] == refc.kv_state_hash(
        [tx for h in range(1, 50) for tx in chain.txs_at[h]])
    for h in range(1, 50):
        assert node.ex.state_store.load_validators(h).hash() == chain.sets[h].hash
    # the stale commit the benchmark's warm-up serves: valid under the set of
    # the height before, and refused under its own wherever the mover changed
    # places (a mover that already sat first keeps every position: the
    # fixture passes such a height over)
    stale = refc.one_height_stale(chain.sets)
    for h in chain.first_heights_of_power_sets():
        forged = chain.stale_commit(h)
        assert ref.commit_verdict(chain.commit_data(h, forged, stale))[0] is True
        moved = chain.sets[h].pubkeys[:5] != chain.sets[h - 1].pubkeys[:5]
        assert ref.commit_verdict(chain.commit_data(h, forged))[0] is not moved
        assert fixtures_churn.stale_commit_is_telling(chain, h) is moved
    assert any(fixtures_churn.stale_commit_is_telling(chain, h)
               for h in chain.first_heights_of_power_sets())


@pytest.mark.asyncio
async def test_range_sync_through_validator_rotation(spy):
    """Through the reactor's own loop and a peer stand-in: a join inside the
    window. Every block applied, nobody punished, nothing verified one
    commit at a time."""
    chain = await _build(CASES["join"])
    node = await spy.start(chain.genesis, window=N_BLOCKS)
    ch, reactor = node.ch, node.reactor
    status = bsm.StatusResponse(chain.store.height(), chain.store.base())

    async def serve():
        while True:
            env = await ch.out_q.get()
            msg = env.message
            if isinstance(msg, bsm.StatusRequest):
                await ch.in_q.put(Envelope(BLOCKSYNC_CHANNEL, status, from_="peer0"))
            elif isinstance(msg, bsm.BlockRequest):
                blk = chain.store.load_block(msg.height)
                if blk is not None:
                    await ch.in_q.put(
                        Envelope(BLOCKSYNC_CHANNEL, bsm.BlockResponse(blk), from_="peer0"))

    server = asyncio.get_running_loop().create_task(serve())
    await node.peer_q.put(PeerUpdate("peer0", PeerStatus.UP))
    await reactor.start()
    try:
        await asyncio.wait_for(reactor.synced.wait(), timeout=120)
    finally:
        server.cancel()
        await reactor.stop()
        await node.conns.stop()

    assert node.bstore.height() >= N_BLOCKS - 1
    final_vals = node.ex.state_store.load_validators(node.bstore.height())
    assert final_vals is not None and len(final_vals) == N_VALS + 1
    assert node.punished() == []
    assert reactor.metrics["blocks_applied"] >= N_BLOCKS - 1
    assert reactor.metrics["sequential_blocks"] == 0 and node.singles == []
    assert reactor.metrics["cuts"] >= 1
    assert {h for c in node.calls for h, _s in c} == set(range(1, node.bstore.height() + 1))
