"""Apply derives each thing once (ISSUE 42): a commit's signatures are
serialised once per Commit object however many times the block's apply asks
for its bytes or its hash, a key's address is derived once per key object,
and `_exec_block` takes the LastCommit's signers from the state when the
state is one height behind the block — from the state store only under a
tip state (handshake replay).

Counted through the real `BlockSyncReactor` over a 150-validator kvstore
chain (the benchmark's seeded fixture at a small length, host route), and
held byte for byte to what the same code writes with every memo bypassed."""

import asyncio
import copy
import dataclasses
import pickle

import pytest

from benchmark import fixtures, fixtures_churn, harness
from benchmark.drivers import blocksync as bs_driver
from tendermint_tpu import crypto
from tendermint_tpu import testing as tt
from tendermint_tpu.abci.kvstore import KVStoreApp
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.crypto import hashes
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.libs import trace
from tendermint_tpu.proxy import AppConns
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import state_from_genesis
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store.blockstore import BlockStore
from tendermint_tpu.store.db import MemDB
from tendermint_tpu.types.block import Block, Commit, CommitSig
from tendermint_tpu.types.genesis import GenesisDoc

SEED = 3000004201
N_VALS, POWER, N_BLOCKS = 150, 10, 26

#: where each memo lives (`__dict__` of the object it describes)
COMMIT_MEMOS = ("_sig_bytes", "_encoded", "_hash")
BLOCK_MEMOS = ("_encoded",)
KEY_MEMOS = ("_address",)


@pytest.fixture(scope="module")
def chain150():
    return asyncio.run(
        fixtures.kvstore_chain(SEED, "once", N_BLOCKS, N_VALS, POWER, 2))


@pytest.fixture
def recorder():
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.enabled = old
    trace.RECORDER.clear()


class Counts:
    """Counting wrappers, hung on the classes by `install` for the rest of
    the test (monkeypatch takes them off)."""

    def __init__(self, monkeypatch):
        self.monkeypatch = monkeypatch
        self.sig_encodes = 0
        self.address_derivations = 0
        self.load_validators = 0
        self.commits = {}  # id -> the Commit (kept alive: ids are not reused)
        self.applied_at = []  # address derivations so far, after each applied block

    def install(self):
        sig_encode, derive = CommitSig.encode, hashes.address
        commit_encode, commit_hash = Commit.encode, Commit.hash
        load = StateStore.load_validators

        def counted_sig_encode(cs):
            self.sig_encodes += 1
            return sig_encode(cs)

        def counted_derive(data):
            self.address_derivations += 1
            return derive(data)

        def seen(fn):
            def wrapper(commit):
                self.commits[id(commit)] = commit
                return fn(commit)
            return wrapper

        def counted_load(store, height):
            self.load_validators += 1
            return load(store, height)

        self.monkeypatch.setattr(CommitSig, "encode", counted_sig_encode)
        self.monkeypatch.setattr(hashes, "address", counted_derive)
        self.monkeypatch.setattr(Commit, "encode", seen(commit_encode))
        self.monkeypatch.setattr(Commit, "hash", seen(commit_hash))
        self.monkeypatch.setattr(StateStore, "load_validators", counted_load)


def _fresh_genesis(chain):
    """The chain's genesis through its JSON: new key objects, no memo on
    any (the builder's own carry the addresses it derived)."""
    return GenesisDoc.from_json(chain.genesis.to_json())


async def _apply_in_order(chain, bstore, ex, state):
    """The reactor's own per-block sequence over the whole chain but its
    last block (whose commit no later block carries)."""
    blocks = [chain.block(h) for h in range(1, chain.n_blocks + 1)]
    for block, nxt in zip(blocks, blocks[1:]):
        parts = block.make_part_set()
        bstore.save_block(block, parts, nxt.last_commit)
        state, _ = await ex.apply_block(
            state, block.block_id(parts.header), block, commit_verified=True)
    return state


def _exec_rows(rec):
    return [s for s in rec.dump() if (s["subsystem"], s["name"]) == ("state", "exec")]


@pytest.mark.asyncio
async def test_block_sync_serialises_a_commit_once_and_derives_an_address_once(
        chain150, recorder, monkeypatch):
    chain = dataclasses.replace(chain150, genesis=_fresh_genesis(chain150))
    counts = Counts(monkeypatch)
    fresh_node = fixtures.fresh_node

    async def holding(genesis):
        # counted from the node's first line: the handshake at genesis sorts
        # the set by address, which is where a fresh key derives its own
        counts.install()
        node = await fresh_node(genesis)
        ex = node[4]
        apply_block = ex.apply_block

        async def applying(*a, **kw):
            res = await apply_block(*a, **kw)
            counts.applied_at.append(counts.address_derivations)
            return res

        ex.apply_block = applying
        recorder.clear()
        return node

    monkeypatch.setattr(fixtures, "fresh_node", holding)
    cell = {"traffic": {"peers": 4, "window": 64, "trace_seconds": 0.1}}
    vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=8192)
    try:
        s = await bs_driver._sync(chain, cell, 120.0, harness.Spans())
    finally:
        vh.release_hub()
    applied = len(s.applied)
    assert applied >= 20 and not s.refused and not s.peer_errors
    assert s.app_hash == chain.app_hash_at[s.final_height]

    # every Commit object the sync asked for bytes or a hash of was
    # serialised exactly once: 150 CommitSig.encode a commit object, however
    # many of the five uses (part set, block size, seen commit, canonical
    # commit, last_commit_hash) touched it
    assert applied <= len(counts.commits) <= N_BLOCKS
    assert counts.sig_encodes == N_VALS * len(counts.commits)

    # a static set: each of the node's 150 key objects derives its address
    # once in the node's life, none of them after the first applied block
    assert counts.address_derivations == N_VALS
    assert counts.applied_at[0] == counts.applied_at[-1] == N_VALS

    # the signers of LastCommit come from the state, never the store
    assert counts.load_validators == 0
    rows = _exec_rows(recorder)
    assert len(rows) == applied
    assert [r["attrs"].get("last_vals") for r in rows] == [None] + ["state"] * (applied - 1)


class RecordingApp(KVStoreApp):
    def __init__(self):
        super().__init__()
        self.last_commit_infos = {}

    def begin_block(self, req):
        self.last_commit_infos[req.header.height] = req.last_commit_info
        return super().begin_block(req)


@pytest.mark.asyncio
async def test_replay_under_a_tip_state_reads_the_store_and_gives_the_same_last_commit_info(
        recorder, monkeypatch):
    """A committee that changes every 4 heights: `LastCommitInfo` from the
    state's set (apply) equals, field for field and height for height, the
    one from the store's set (handshake replay under the tip state)."""
    n = 22
    chain = await fixtures_churn.churn_chain(SEED, "replay", n, 7, POWER, 2, 4, 4)
    assert set(chain.changes.values()) == {"power", "swap"}
    # apply: a node at genesis takes the chain block by block
    first = RecordingApp()
    conns = AppConns.local(first)
    bstore, sstore = BlockStore(MemDB()), StateStore(MemDB())
    genesis_state = state_from_genesis(chain.genesis)
    state = await Handshaker(sstore, genesis_state, bstore, chain.genesis).handshake(conns)
    sstore.save(state)
    ex = BlockExecutor(sstore, conns.consensus, block_store=bstore)
    recorder.clear()
    await _apply_in_order(chain, bstore, ex, state)
    await conns.stop()
    tip = n - 1
    assert first.app_hash == chain.app_hash_at[tip]
    assert {r["attrs"].get("last_vals") for r in _exec_rows(recorder)} == {None, "state"}

    # replay: the same stores, a tip state, an app that lost everything
    counts = Counts(monkeypatch)
    counts.install()
    recorder.clear()
    second = RecordingApp()
    conns = AppConns.local(second)
    replayed = await Handshaker(sstore, sstore.load(), bstore, chain.genesis).handshake(conns)
    await conns.stop()
    assert replayed.last_block_height == tip and second.app_hash == first.app_hash
    assert counts.load_validators == tip - 1  # every height above the initial one
    rows = _exec_rows(recorder)
    assert [r["attrs"].get("last_vals") for r in rows] == [None] + ["store"] * (tip - 1)
    assert set(second.last_commit_infos) == set(first.last_commit_infos) == set(range(1, n))
    for h in range(1, n):
        a, b = first.last_commit_infos[h], second.last_commit_infos[h]
        assert a == b and dataclasses.asdict(a) == dataclasses.asdict(b), h
    assert any(len({v.power for v in first.last_commit_infos[h].votes}) > 1
               for h in range(2, n)), "no power change reached a LastCommitInfo"


# -- byte identity -----------------------------------------------------------------


def _commit(flags: str, agg: bool, n: int = 6) -> Commit:
    """A signed commit whose signatures follow `flags` (c commit, n nil,
    a absent), cyclically; `agg` strips them into the aggregate form."""
    vals, keys = tt.make_validator_set(n, seed=b"once-" + flags.encode())
    kinds = [flags[i % len(flags)] for i in range(n)]
    commit = tt.make_commit(
        "once", 5, 1, tt.make_block_id(b"once"), vals, keys,
        nil_indices=frozenset(i for i, k in enumerate(kinds) if k == "n"),
        absent_indices=frozenset(i for i, k in enumerate(kinds) if k == "a"),
    )
    if agg:
        commit = dataclasses.replace(
            commit,
            signatures=tuple(
                cs if cs.is_absent() else dataclasses.replace(cs, signature=b"")
                for cs in commit.signatures),
            agg_sig=bytes(range(96)),
        )
    return commit


def _twin(commit: Commit) -> Commit:
    """The same commit built again from its fields: no memo on it."""
    return Commit(commit.height, commit.round, commit.block_id,
                  tuple(CommitSig(cs.flag, cs.validator_address, cs.timestamp_ns, cs.signature)
                        for cs in commit.signatures), commit.agg_sig)


def _unmemoised(obj, names, fn):
    for name in names:
        obj.__dict__.pop(name, None)
    return fn()


COMMIT_SHAPES = [(flags, agg) for flags in ("c", "n", "a", "cna", "ccan") for agg in (False, True)]


@pytest.mark.parametrize("flags,agg", COMMIT_SHAPES)
def test_memoised_commit_bytes_and_hash_equal_a_fresh_twin_s(flags, agg):
    commit = _commit(flags, agg)
    first = (commit.encode(), commit.hash())
    assert set(COMMIT_MEMOS) <= set(commit.__dict__)
    again = (commit.encode(), commit.hash())  # memo reads
    assert again == first and again[0] is first[0]
    twin = _twin(commit)
    assert not set(COMMIT_MEMOS) & set(twin.__dict__)
    # the twin asked in the other order: hash first, then bytes
    assert (twin.hash(), twin.encode()) == (first[1], first[0])
    assert Commit.decode(first[0]) == commit == twin
    # and with every memo dropped before each call
    assert _unmemoised(commit, COMMIT_MEMOS, commit.encode) == first[0]
    assert _unmemoised(commit, COMMIT_MEMOS, commit.hash) == first[1]


@pytest.mark.parametrize("flags,agg", COMMIT_SHAPES)
def test_replace_on_a_memoised_commit_yields_the_new_bytes(flags, agg):
    commit = _commit(flags, agg)
    old = (commit.encode(), commit.hash())
    sigs = commit.signatures[1:] + commit.signatures[:1]
    for changed in (dataclasses.replace(commit, round=commit.round + 1),
                    dataclasses.replace(commit, signatures=sigs)):
        assert not set(COMMIT_MEMOS) & set(changed.__dict__)
        fresh = _twin(changed)
        assert (changed.encode(), changed.hash()) == (fresh.encode(), fresh.hash())
        # (six absent signatures rotated are the same six)
        assert (changed.encode() != old[0]) == (changed != commit)
        assert Commit.decode(changed.encode()) == changed
    assert (commit.encode(), commit.hash()) == old


@pytest.mark.parametrize("flags,agg", COMMIT_SHAPES)
@pytest.mark.parametrize("how", ["copy", "deepcopy", "pickle"])
def test_a_copy_of_a_memoised_commit_is_equal_and_serialises_alike(flags, agg, how):
    commit = _commit(flags, agg)
    want = (commit.encode(), commit.hash())
    other = {"copy": copy.copy, "deepcopy": copy.deepcopy,
             "pickle": lambda c: pickle.loads(pickle.dumps(c))}[how](commit)
    assert other == commit and hash(other) == hash(commit)
    assert (other.encode(), other.hash()) == want
    assert _unmemoised(other, COMMIT_MEMOS, other.encode) == want[0]


def test_block_bytes_are_kept_and_replace_makes_new_ones(chain150):
    block = chain150.block(3)
    raw = block.encode()
    assert block.encode() is raw and set(BLOCK_MEMOS) <= set(block.__dict__)
    assert chain150.block(3).encode() == raw
    assert block.make_part_set().assemble() == raw
    for changed in (dataclasses.replace(block, txs=block.txs + (b"more=1",)),
                    dataclasses.replace(block, last_commit=_twin(
                        dataclasses.replace(block.last_commit, round=1)))):
        assert not set(BLOCK_MEMOS) & set(changed.__dict__)
        assert changed.encode() != raw
        assert Block.decode(changed.encode()) == changed
        assert changed.encode() == Block(changed.header, changed.txs, changed.evidence,
                                         changed.last_commit).encode()
    for other in (copy.copy(block), pickle.loads(pickle.dumps(block))):
        assert other == block and other.encode() == raw
        assert _unmemoised(other, BLOCK_MEMOS, other.encode) == raw


@pytest.mark.parametrize("key_type", ["ed25519", "secp256k1", "sr25519", "bls12381"])
def test_a_key_keeps_the_address_its_bytes_give(key_type):
    vals, _ = tt.make_validator_set(2, seed=b"once-key", key_types=(key_type,))
    for val in vals.validators:
        key = val.pub_key
        want = hashes.sha256(key.bytes())[:20]
        twin = crypto.pubkey_from_type_and_bytes(key.TYPE, key.bytes())
        assert not set(KEY_MEMOS) & set(twin.__dict__)
        assert twin.address() == want and twin.address() is twin.address()
        assert key.address() == want == val.address
        assert set(KEY_MEMOS) <= set(key.__dict__)
        for other in (copy.copy(key), copy.deepcopy(key), pickle.loads(pickle.dumps(key))):
            assert other == key and hash(other) == hash(key) and other.address() == want
            assert _unmemoised(other, KEY_MEMOS, other.address) == want
    # a copied set shares its keys: no derivation for the copy
    shared = vals.copy()
    assert all(a.pub_key is b.pub_key for a, b in zip(vals.validators, shared.validators))


def _bypass_every_memo(monkeypatch):
    """Every use derives anew, as before ISSUE 42: the memos are dropped
    before each call that would read them."""
    def dropping(cls, method, names):
        fn = getattr(cls, method)

        def wrapper(self, *a, **kw):
            for name in names:
                self.__dict__.pop(name, None)
            return fn(self, *a, **kw)
        monkeypatch.setattr(cls, method, wrapper)

    dropping(Commit, "encode", COMMIT_MEMOS)
    dropping(Commit, "hash", COMMIT_MEMOS)
    dropping(Block, "encode", BLOCK_MEMOS)
    dropping(crypto.PubKey, "address", KEY_MEMOS)


async def _sequence(chain, monkeypatch, *, bypass: bool):
    """The reactor's own per-block sequence into fresh MemDB stores; what
    both stores hold at the end, key for key."""
    with monkeypatch.context() as mp:
        if bypass:
            _bypass_every_memo(mp)
        app, conns, bstore, state, ex = await fixtures.fresh_node(_fresh_genesis(chain))
        await _apply_in_order(chain, bstore, ex, state)
        await conns.stop()
        assert app.app_hash == chain.app_hash_at[chain.n_blocks - 1]
        return dict(bstore.db.iterate()), dict(ex.state_store.db.iterate())


@pytest.mark.asyncio
async def test_both_stores_hold_the_bytes_they_hold_with_every_memo_bypassed(
        chain150, monkeypatch):
    counted = Counts(monkeypatch)
    counted.install()
    kept = await _sequence(chain150, monkeypatch, bypass=False)
    with_memo = counted.sig_encodes
    plain = await _sequence(chain150, monkeypatch, bypass=True)
    without = counted.sig_encodes - with_memo
    for ours, theirs in zip(kept, plain):
        assert ours.keys() == theirs.keys() and len(ours) > 3 * (N_BLOCKS - 1)
        assert all(ours[k] == theirs[k] for k in ours)
    # the bypass really bypassed: five serialisations of a commit, not one
    assert with_memo == N_VALS * (N_BLOCKS - 1)
    assert without >= 4 * with_memo


def test_first_use_from_many_threads_gives_every_caller_the_same_bytes():
    """The memos are written without a lock: two threads may both derive,
    each stores the same value. Sixteen threads (more than cores) at a
    shortened switch interval ask a fresh commit and a fresh key at once."""
    import sys
    import threading

    want_commit = _commit("ccan", False, n=40)
    want = (want_commit.encode(), want_commit.hash())
    key = tt.make_validator_set(1, seed=b"once-thread")[0].validators[0].pub_key
    want_addr = hashes.sha256(key.bytes())[:20]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            commit = _twin(want_commit)
            fresh_key = crypto.pubkey_from_type_and_bytes(key.TYPE, key.bytes())
            got, start = [], threading.Barrier(16)

            def ask(i):
                start.wait(timeout=10)
                got.append((commit.hash(), commit.encode()) if i % 2 else
                           (commit.encode(), commit.hash())[::-1])
                got.append(fresh_key.address())

            threads = [threading.Thread(target=ask, args=(i,)) for i in range(16)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads) and len(got) == 32
            assert {g for g in got if isinstance(g, tuple)} == {(want[1], want[0])}
            assert {g for g in got if isinstance(g, bytes)} == {want_addr}
    finally:
        sys.setswitchinterval(old)
