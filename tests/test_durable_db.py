"""Stores that are on disk (ISSUE 43): the `DB` contract with `sync=`, the
crash that takes back a DB's unsynced tail (`ChaosDB.simulate_crash`), a
catch-up crashed at every kind of write boundary and handshaken back, and
the kvstore app persisting what a block changed.

The chain is the benchmark's seeded kvstore chain at a tiny size; the apply
loop is the block-sync reactor's own order (`_apply_one`: save the block,
then ApplyBlock: responses, the app's commit, the state)."""

import json

import pytest

from benchmark import fixtures
from tendermint_tpu.abci import types as abci
from tendermint_tpu.abci.kvstore import KVStoreApp
from tendermint_tpu.consensus.replay import Handshaker
from tendermint_tpu.libs import trace
from tendermint_tpu.libs.chaosfs import ChaosDB, ChaosFS
from tendermint_tpu.libs.metrics import NodeMetrics
from tendermint_tpu.proxy import AppConns
from tendermint_tpu.state.execution import BlockExecutor
from tendermint_tpu.state.state import state_from_genesis
from tendermint_tpu.state.store import StateStore
from tendermint_tpu.store import db as dbm
from tendermint_tpu.store.blockstore import BlockStore
from tendermint_tpu.store.db import MemDB, SQLiteDB, open_node_stores

SEED = 3000004301
KINDS = ("mem", "sqlite-unsynced", "sqlite-synced")


def _open(kind, tmp_path, name=""):
    return MemDB() if kind == "mem" else SQLiteDB(str(tmp_path / "kv.db"), name)


def _sync(kind) -> bool:
    return kind == "sqlite-synced"


# -- the DB contract --------------------------------------------------------------


@pytest.mark.parametrize("kind", KINDS)
class TestContract:
    def test_set_get_has_delete(self, kind, tmp_path):
        db = _open(kind, tmp_path)
        assert db.get(b"a") is None and not db.has(b"a")
        db.set(b"a", b"1", sync=_sync(kind))
        db.set(b"a", b"2", sync=_sync(kind))
        assert db.get(b"a") == b"2" and db.has(b"a")
        db.delete(b"a")
        assert db.get(b"a") is None

    def test_ordered_iteration(self, kind, tmp_path):
        db = _open(kind, tmp_path)
        keys = [b"h:" + i.to_bytes(8, "big") for i in (5, 1, 300, 2, 256)]
        db.write_batch([(k, k[-1:]) for k in keys] + [(b"g", b"x"), (b"i", b"y")],
                       sync=_sync(kind))
        got = [k for k, _ in db.iterate(b"h:", b"h;")]
        assert got == sorted(keys)
        assert [k for k, _ in db.iterate(b"h:", b"h;", reverse=True)] == sorted(keys)[::-1]
        assert [k for k, _ in db.iterate(b"h:" + (256).to_bytes(8, "big"))] == [
            keys[4], keys[2], b"i"]
        assert list(db.iterate(b"z")) == []

    def test_batch_sets_then_deletes(self, kind, tmp_path):
        db = _open(kind, tmp_path)
        db.set(b"old", b"0")
        db.write_batch([(b"a", b"1"), (b"b", b"2")], [b"old", b"b"], sync=_sync(kind))
        assert dict(db.iterate()) == {b"a": b"1"}

    def test_a_failed_batch_applies_nothing(self, kind, tmp_path):
        """All of a batch or none of it — and no row of it rides the next
        commit."""
        db = _open(kind, tmp_path)
        if kind == "mem":
            # nothing in a MemDB batch can fail half-way: the whole of it
            # lands under one lock
            db.write_batch([(b"a", b"1")], [b"a"])
            assert list(db.iterate()) == []
            return
        with pytest.raises(Exception):
            db.write_batch([(b"a", b"1"), (b"b", None)], sync=_sync(kind))
        db.set(b"c", b"3", sync=_sync(kind))
        assert dict(db.iterate()) == {b"c": b"3"}

    def test_reopen_reads_back(self, kind, tmp_path):
        db = _open(kind, tmp_path)
        db.write_batch([(b"a", b"1"), (b"b", b"2")], sync=_sync(kind))
        db.set(b"c", b"3", sync=_sync(kind))
        if kind == "mem":
            assert dict(db.iterate()) == {b"a": b"1", b"b": b"2", b"c": b"3"}
            return
        db.close()
        again = _open(kind, tmp_path)
        assert dict(again.iterate()) == {b"a": b"1", b"b": b"2", b"c": b"3"}
        again.close()


class _Spy(SQLiteDB):
    """Reads the connection's `synchronous` INSIDE the transaction, right
    before its commit (0 off, 1 NORMAL, 2 FULL)."""

    def __init__(self, path):
        self.seen = []
        super().__init__(path, "spy")

    def _commit(self, sync):
        level = self._conn.execute("PRAGMA synchronous").fetchone()[0]
        self.seen.append((sync, self._conn.in_transaction, level))
        super()._commit(sync)


class TestSQLiteSync:
    def test_synchronous_is_full_inside_a_synced_commit_and_normal_elsewhere(self, tmp_path):
        db = _Spy(str(tmp_path / "s.db"))
        assert db._conn.execute("PRAGMA journal_mode").fetchone()[0] == "wal"
        db.set(b"a", b"1")
        db.set(b"b", b"2", sync=True)
        db.write_batch([(b"c", b"3")], sync=True)
        db.write_batch([(b"d", b"4")])
        db.delete(b"a")
        db.write_batch([(b"e", b"5")], [b"b"], sync=True)
        assert db.seen == [
            (False, True, 1), (True, True, 2), (True, True, 2),
            (False, True, 1), (False, True, 1), (True, True, 2)]
        assert dict(db.iterate()) == {b"c": b"3", b"d": b"4", b"e": b"5"}

    def test_a_named_db_counts_and_records(self, tmp_path):
        old = trace.RECORDER.enabled
        trace.RECORDER.enabled = True
        trace.RECORDER.clear()
        try:
            stores = open_node_stores(str(tmp_path / "data"))
            before = {n: dict(c) for n, c in dbm.COUNTERS.items()}
            stores.block_db.write_batch([(b"k1", b"v" * 10), (b"k2", b"w" * 20)], sync=True)
            stores.state_db.set(b"key", b"value", sync=True)
            stores.app_db.write_batch([(b"k", b"v")], [b"gone"])
            stores.state_db.get(b"key")
            list(stores.state_db.iterate(b"k"))
            delta = {n: {k: dbm.COUNTERS[n][k] - before[n][k] for k in c}
                     for n, c in dbm.COUNTERS.items() if n in ("block", "state", "app")}
            assert delta == {
                "block": {"sync_commits": 1, "bytes_written": 34, "gets": 0},
                "state": {"sync_commits": 1, "bytes_written": 8, "gets": 2},
                "app": {"sync_commits": 0, "bytes_written": 2, "gets": 0},
            }
            rows = [r for r in trace.RECORDER.dump() if r["subsystem"] == "db"]
            writes = [r for r in rows if r["name"] == "write"]
            assert [r["attrs"] for r in writes] == [
                {"db": "block", "rows": 2, "bytes": 34, "sync": True},
                {"db": "state", "rows": 1, "bytes": 8, "sync": True},
                {"db": "app", "rows": 2, "bytes": 2, "sync": False},
            ]
            syncs = [r for r in rows if r["name"] == "sync"]
            assert [r["attrs"]["db"] for r in syncs] == ["block", "state"]
            assert [r["parent_id"] for r in syncs] == [w["span_id"] for w in writes[:2]]
            text = NodeMetrics().render()
            for line in ('tendermint_tpu_db_sync_commits_total{db="block"}',
                         'tendermint_tpu_db_bytes_written_total{db="state"}',
                         'tendermint_tpu_db_gets_total{db="app"}'):
                assert line in text
            stores.close()
        finally:
            trace.RECORDER.enabled = old
            trace.RECORDER.clear()

    def test_an_unnamed_db_records_nothing(self, tmp_path):
        trace.RECORDER.clear()
        before = json.dumps(dbm.COUNTERS, sort_keys=True)
        db = SQLiteDB(str(tmp_path / "plain.db"))
        db.set(b"a", b"1", sync=True)
        db.get(b"a")
        assert json.dumps(dbm.COUNTERS, sort_keys=True) == before
        assert not [r for r in trace.RECORDER.dump() if r["subsystem"] == "db"]

    def test_the_stores_sync_where_the_reference_does(self, tmp_path):
        """save_block, save_abci_responses and save are synced; the rest
        is not."""
        from tendermint_tpu.state.store import ABCIResponses

        class Log(MemDB):
            def __init__(self):
                super().__init__()
                self.log = []

            def set(self, key, value, sync=False):
                self.log.append(("set", sync))
                super().set(key, value, sync)

            def write_batch(self, sets, deletes=(), sync=False):
                self.log.append(("batch", sync))
                super().write_batch(sets, deletes, sync)

        import asyncio

        chain = asyncio.run(fixtures.kvstore_chain(SEED, "syncs", 3, 4, 10, 1))
        bdb, sdb = Log(), Log()
        bstore, sstore = BlockStore(bdb), StateStore(sdb)
        block = chain.block(1)
        bstore.save_block(block, block.make_part_set(), chain.commit(1))
        bstore.save_seen_commit(1, chain.commit(1))
        assert bdb.log == [("batch", True), ("set", False)]
        state = state_from_genesis(chain.genesis)
        sstore.save(state)
        sstore.save_abci_responses(1, ABCIResponses())
        sstore.save_validators(7, state.validators)
        sstore.prune_states(1)
        assert sdb.log == [("batch", True), ("set", True), ("set", False), ("batch", False)]


# -- the crash that drops the unsynced tail ----------------------------------------


@pytest.mark.parametrize("inner", ("mem", "sqlite"))
class TestSimulateCrash:
    def _db(self, inner, tmp_path):
        fs = ChaosFS()
        return fs, fs.wrap_db(MemDB() if inner == "mem" else SQLiteDB(str(tmp_path / "c.db")))

    def test_everything_since_the_last_synced_write_goes(self, inner, tmp_path):
        fs, db = self._db(inner, tmp_path)
        db.set(b"a", b"1")  # unsynced, but BEFORE a synced write: durable with it
        db.write_batch([(b"b", b"2"), (b"c", b"3")], sync=True)
        db.set(b"a", b"changed")
        db.write_batch([(b"d", b"4")], [b"b"])
        db.delete(b"c")
        assert dict(db.iterate()) == {b"a": b"changed", b"d": b"4"}
        assert db.simulate_crash() == 3
        assert dict(db.iterate()) == {b"a": b"1", b"b": b"2", b"c": b"3"}
        assert fs.faults["db_crash_lost_writes"] == 3
        assert db.simulate_crash() == 0  # nothing left to lose

    def test_a_synced_write_survives_and_whole_batches_go(self, inner, tmp_path):
        _fs, db = self._db(inner, tmp_path)
        db.write_batch([(b"x", b"1"), (b"y", b"1")])
        db.simulate_crash()
        assert list(db.iterate()) == []  # both rows of the batch, not one
        db.set(b"x", b"2", sync=True)
        db.simulate_crash()
        assert dict(db.iterate()) == {b"x": b"2"}


# -- a catch-up crashed at every kind of write boundary ------------------------------

N_BLOCKS, CRASH_HEIGHT = 30, 17
#: the writes of one height on the block-sync apply path, in the
#: reference's order: the block (reactor), the ABCI responses, the app's
#: commit, the state
HEIGHT_WRITES = [("block", True), ("state", True), ("app", False), ("state", True)]
BOUNDARIES = {"after_block": 1, "after_responses": 2, "after_app_commit": 3,
              "after_state": 4}


class _Crash(BaseException):
    pass


class _Node:
    """Three ChaosDBs that log every write and can crash after the n-th."""

    def __init__(self, genesis):
        self.genesis = genesis
        self.fs = ChaosFS()
        self.log: list = []
        self.crash_after = -1
        self.dbs = {name: self._logging(name) for name in ("block", "state", "app")}

    def _logging(self, name):
        node = self

        class Logged(ChaosDB):
            def _after(self, sync):
                node.log.append((name, sync))
                if len(node.log) == node.crash_after:
                    raise _Crash()

            def set(self, key, value, sync=False):
                super().set(key, value, sync)
                self._after(sync)

            def write_batch(self, sets, deletes=(), sync=False):
                super().write_batch(sets, deletes, sync)
                self._after(sync)

        return Logged(self.fs, MemDB())

    async def open(self):
        """What a (re)started node does: stores over the DBs, the app over
        its DB, the handshake."""
        self.app = KVStoreApp(self.dbs["app"])
        self.conns = AppConns.local(self.app)
        self.bstore = BlockStore(self.dbs["block"])
        self.sstore = StateStore(self.dbs["state"])
        state = self.sstore.load() or state_from_genesis(self.genesis)
        self.hs = Handshaker(self.sstore, state, self.bstore, self.genesis)
        self.state = await self.hs.handshake(self.conns)
        self.ex = BlockExecutor(self.sstore, self.conns.consensus, block_store=self.bstore)

    async def catch_up(self, chain, to):
        """`BlockSyncReactor._apply_one`'s order, a block at a time."""
        for h in range(self.state.last_block_height + 1, to + 1):
            block = chain.block(h)
            parts = block.make_part_set()
            if self.bstore.height() < h:
                self.bstore.save_block(block, parts, chain.commit(h))
            self.state, _ = await self.ex.apply_block(
                self.state, block.block_id(parts.header), block, commit_verified=True)


@pytest.fixture(scope="module")
def crash_chain():
    import asyncio

    return asyncio.run(fixtures.kvstore_chain(SEED, "crash", N_BLOCKS + 1, 4, 10, 2))


class TestCrashedCatchUp:
    @pytest.mark.asyncio
    async def test_a_height_writes_in_the_references_order(self, crash_chain):
        node = _Node(crash_chain.genesis)
        await node.open()
        node.log.clear()
        await node.catch_up(crash_chain, N_BLOCKS)
        assert node.log == HEIGHT_WRITES * N_BLOCKS
        assert node.app.app_hash == crash_chain.app_hash_at[N_BLOCKS]

    @pytest.mark.parametrize("lose_unsynced", (True, False), ids=("power_loss", "kill"))
    @pytest.mark.parametrize("boundary", sorted(BOUNDARIES))
    @pytest.mark.asyncio
    async def test_crash_reopen_handshake_sync_to_the_end(
            self, crash_chain, boundary, lose_unsynced):
        node = _Node(crash_chain.genesis)
        await node.open()
        node.log.clear()
        node.crash_after = 4 * (CRASH_HEIGHT - 1) + BOUNDARIES[boundary]
        with pytest.raises(_Crash):
            await node.catch_up(crash_chain, N_BLOCKS)
        assert node.log[-1] == HEIGHT_WRITES[BOUNDARIES[boundary] - 1]
        node.crash_after = -1
        if lose_unsynced:
            lost = {name: db.simulate_crash() for name, db in node.dbs.items()}
            # the stores' writes on this path are all synced; the app's never
            # are: it is back at nothing, whatever the boundary
            assert lost["block"] == lost["state"] == 0 and lost["app"] > 0
            assert list(node.dbs["app"].iterate()) == []
        await node.open()
        applied = CRASH_HEIGHT if boundary == "after_state" else CRASH_HEIGHT - 1
        if lose_unsynced:
            # InitChain again, then every block the store holds
            assert node.hs.n_blocks_replayed == CRASH_HEIGHT
            assert len(node.app.validators) == 4
        else:
            # the app kept its commits: at most the tip block is run again
            assert node.hs.n_blocks_replayed == CRASH_HEIGHT - applied
        assert node.state.last_block_height == node.bstore.height() == CRASH_HEIGHT
        assert node.app.height == CRASH_HEIGHT
        assert node.app.app_hash == node.state.app_hash == crash_chain.app_hash_at[CRASH_HEIGHT]
        await node.catch_up(crash_chain, N_BLOCKS)
        assert node.app.app_hash == crash_chain.app_hash_at[N_BLOCKS]
        assert node.sstore.load().last_block_height == N_BLOCKS
        for h in (1, CRASH_HEIGHT, N_BLOCKS):
            assert node.bstore.load_block_meta(h).block_id.hash == crash_chain.block_hash_at[h]


# -- the app persists what a block changed ---------------------------------------------


async def _run_blocks(app, first, last, txs_of=lambda h: [b"k%d=v%d" % (h, h)]):
    for h in range(first, last + 1):
        app.begin_block(None)
        for tx in txs_of(h):
            assert app.deliver_tx(abci.RequestDeliverTx(tx)).code == 0
        app.end_block(abci.RequestEndBlock(h))
        app.commit()


VAL_A, VAL_B = bytes(range(32)), bytes(range(1, 33))


def _val_tx(pub, power):
    return b"val:" + pub.hex().encode() + b"!" + str(power).encode()


class TestKVStoreOnADB:
    @pytest.mark.asyncio
    async def test_same_app_hash_and_proofs_as_over_none(self, tmp_path):
        plain, on_disk = KVStoreApp(), KVStoreApp(SQLiteDB(str(tmp_path / "app.db"), "app"))
        for app in (plain, on_disk):
            await _run_blocks(app, 1, 12)
        assert plain.app_hash == on_disk.app_hash and plain.items == on_disk.items
        q = abci.RequestQuery(data=b"k7", prove=True)
        a, b = plain.query(q), on_disk.query(q)
        assert a.value == b.value == b"v7" and a.proof_ops == b.proof_ops and a.proof_ops
        assert plain.list_snapshots() == on_disk.list_snapshots()

    @pytest.mark.asyncio
    async def test_reload_equals_validators_included(self, tmp_path):
        path = str(tmp_path / "app.db")
        app = KVStoreApp(SQLiteDB(path))
        app.init_chain(abci.RequestInitChain(
            0, "c", None, (abci.ValidatorUpdate("ed25519", VAL_A, 10),
                           abci.ValidatorUpdate("ed25519", VAL_B, 10)), b"", 1))
        await _run_blocks(app, 1, 5)
        await _run_blocks(app, 6, 6, lambda h: [_val_tx(VAL_A, 0), _val_tx(VAL_B, 7), b"x=y"])
        assert app.validators == {VAL_B: 7}
        app.db.close()
        again = KVStoreApp(SQLiteDB(path))
        assert (again.items, again.height, again.app_hash, again.validators) == (
            app.items, 6, app.app_hash, {VAL_B: 7})
        assert json.loads(again.info(None).data) == {"size": 6}
        # a key removed and set again within one block ends as the state has it
        await _run_blocks(again, 7, 7, lambda h: [_val_tx(VAL_B, 0), _val_tx(VAL_B, 3)])
        assert KVStoreApp(again.db).validators == again.validators == {VAL_B: 3}

    @pytest.mark.asyncio
    async def test_an_old_format_db_still_loads_and_is_rewritten_in_rows(self, tmp_path):
        db = SQLiteDB(str(tmp_path / "old.db"))
        items = {b"k%d" % i: b"v%d" % i for i in range(9)}
        ref_app = KVStoreApp()
        await _run_blocks(ref_app, 1, 9, lambda h: [b"k%d=v%d" % (h - 1, h - 1)])
        assert ref_app.items == items
        db.set(b"__kvstore_state__", json.dumps({  # what the old `_save` wrote
            "items": {k.hex(): v.hex() for k, v in items.items()},
            "height": 9, "app_hash": ref_app.app_hash.hex(),
            "validators": {VAL_A.hex(): 10}}).encode())
        app = KVStoreApp(db)
        assert (app.items, app.height, app.app_hash, app.validators) == (
            items, 9, ref_app.app_hash, {VAL_A: 10})
        record = json.loads(db.get(b"__kvstore_state__"))
        assert record == {"height": 9, "app_hash": ref_app.app_hash.hex(), "size": 9}
        assert {k: v for k, v in db.iterate(b"kv:", b"kv;")} == {
            b"kv:" + k: v for k, v in items.items()}
        await _run_blocks(app, 10, 10)
        again = KVStoreApp(db)
        assert again.items == app.items and again.height == 10 and again.validators == {VAL_A: 10}

    @pytest.mark.asyncio
    async def test_bytes_a_commit_do_not_grow_with_the_state(self, tmp_path):
        app = KVStoreApp(SQLiteDB(str(tmp_path / "app.db"), "app-grow"))
        c = dbm.COUNTERS["app-grow"]

        async def bytes_of(first, last):
            before = c["bytes_written"]
            await _run_blocks(app, first, last,
                              lambda h: [b"key-%06d-%d=val-%06d" % (h, j, h) for j in (0, 1)])
            return (c["bytes_written"] - before) / (last - first + 1)

        early = await bytes_of(1, 50)
        await bytes_of(51, 950)
        late = await bytes_of(951, 1000)
        assert len(app.items) == 2000
        assert late <= 1.05 * early and late < 400, (early, late)
        # 2 rows a height and the one record, nothing else
        rows = sum(1 for _ in app.db.iterate(b"kv:", b"kv;"))
        assert rows == 2000 and sum(1 for _ in app.db.iterate()) == 2001

    @pytest.mark.asyncio
    async def test_a_snapshot_restore_leaves_no_stale_row(self, tmp_path):
        src = KVStoreApp(snapshot_interval=5)
        await _run_blocks(src, 1, 5)
        snap = src.list_snapshots().snapshots[-1]
        dst = KVStoreApp(SQLiteDB(str(tmp_path / "dst.db")))
        await _run_blocks(dst, 1, 2, lambda h: [b"stale%d=x" % h])
        dst.offer_snapshot(abci.RequestOfferSnapshot(snapshot=snap, app_hash=src.app_hash))
        for i in range(snap.chunks):
            chunk = src.load_snapshot_chunk(
                abci.RequestLoadSnapshotChunk(snap.height, snap.format, i)).chunk
            dst.apply_snapshot_chunk(abci.RequestApplySnapshotChunk(index=i, chunk=chunk))
        again = KVStoreApp(dst.db)
        assert again.items == src.items and again.app_hash == src.app_hash and again.height == 5
