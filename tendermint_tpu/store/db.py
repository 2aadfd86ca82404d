"""Key-value database abstraction (the analog of tm-db used throughout the
reference: block store, state store, evidence pool, indexer all take a DB).

Two implementations: `MemDB` (tests, in-memory transports) and `SQLiteDB`
(durable single-file store, stdlib sqlite3 — the image has no leveldb).
Both support atomic write batches and ordered iteration, which the stores
rely on for height-keyed scans and pruning.

What `sync` means. `set(key, value, sync=True)` and `write_batch(...,
sync=True)` return only once the write would survive the machine losing
power: tm-db's `SetSync` / `WriteSync`. An unsynced write (the default,
tm-db's `Set` / `Write`) is atomic and is seen by every later read, but a
crash may take it back, together with every other unsynced write since the
last synced one on the same DB — never a part of a batch, and never a
synced write or anything written before one. `MemDB` has nothing to sync
and ignores the flag. `SQLiteDB` keeps `journal_mode=WAL` and commits a
synced write under `PRAGMA synchronous=FULL` (SQLite then fsyncs the WAL
before `commit()` returns), an unsynced one under `NORMAL` (no fsync until
the next checkpoint). Who syncs what is the stores' business
(`BlockStore.save_block`, `StateStore.save`, `.save_abci_responses`: where
the reference's stores call `WriteSync` / `SetSync`); there is no switch
that turns syncing off. `libs/chaosfs.ChaosDB.simulate_crash()` is this
contract's crash.

What is counted. A `SQLiteDB` opened with a `name` (`open_node_stores`
names a node's: block, state, app) records a flight-recorder span
`db.write` [db, rows, bytes, sync] around every `set` / `write_batch` /
`delete`, inside it a span `db.sync` around the synced `commit()` alone
(SQLite writes the transaction's WAL frames there, fsyncs the WAL and,
every ≈ 1,000 pages, checkpoints them into the database file — so it is
the price of the commit under FULL, the fsync among it, not the fsync
alone), and counts into `COUNTERS[name]`: `sync_commits`,
`bytes_written` (key + value bytes of the rows set), `gets` (`get` and
`iterate` calls). `libs/metrics.NodeMetrics` renders them as
`db_sync_commits_total{db}`, `db_bytes_written_total{db}`,
`db_gets_total{db}`."""

from __future__ import annotations

import os
import sqlite3
import threading
from dataclasses import dataclass
from typing import Iterator

from ..libs import trace

#: name -> {"sync_commits", "bytes_written", "gets"}: process-wide, like
#: libs/metrics.STORAGE (a DB is opened before, and sometimes without, a
#: NodeMetrics)
COUNTERS: dict[str, dict[str, float]] = {}


def counters_for(name: str) -> dict[str, float]:
    return COUNTERS.setdefault(
        name, {"sync_commits": 0.0, "bytes_written": 0.0, "gets": 0.0}
    )


class DB:
    def get(self, key: bytes) -> bytes | None:
        raise NotImplementedError

    def has(self, key: bytes) -> bool:
        return self.get(key) is not None

    def set(self, key: bytes, value: bytes, sync: bool = False) -> None:
        """Write one key; with `sync`, durably (module docstring)."""
        raise NotImplementedError

    def delete(self, key: bytes) -> None:
        raise NotImplementedError

    def iterate(
        self, start: bytes = b"", end: bytes | None = None, reverse: bool = False
    ) -> Iterator[tuple[bytes, bytes]]:
        """Ordered scan over keys in [start, end)."""
        raise NotImplementedError

    def write_batch(
        self,
        sets: list[tuple[bytes, bytes]],
        deletes: list[bytes] = (),
        sync: bool = False,
    ):
        """Atomically apply sets then deletes; with `sync`, durably."""
        raise NotImplementedError

    def close(self) -> None:
        pass


class MemDB(DB):
    def __init__(self):
        self._data: dict[bytes, bytes] = {}
        self._lock = threading.Lock()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            return self._data.get(key)

    def set(self, key: bytes, value: bytes, sync: bool = False) -> None:
        with self._lock:
            self._data[key] = value

    def delete(self, key: bytes) -> None:
        with self._lock:
            self._data.pop(key, None)

    def iterate(self, start=b"", end=None, reverse=False):
        with self._lock:
            keys = sorted(
                k for k in self._data if k >= start and (end is None or k < end)
            )
        if reverse:
            keys.reverse()
        for k in keys:
            v = self.get(k)
            if v is not None:
                yield k, v

    def write_batch(self, sets, deletes=(), sync: bool = False):
        with self._lock:
            for k, v in sets:
                self._data[k] = v
            for k in deletes:
                self._data.pop(k, None)


class SQLiteDB(DB):
    """Durable KV store; WAL journal mode so reads don't block the writer.
    A synced write commits under `synchronous=FULL`, any other under
    `NORMAL` (module docstring); the pragma is switched between
    transactions, only when the next write's kind differs from the last."""

    def __init__(self, path: str, name: str = ""):
        self.path = path
        self.name = name
        self._counters = counters_for(name) if name else None
        self._conn = sqlite3.connect(path, check_same_thread=False)
        self._lock = threading.Lock()
        self._full = False  # the connection's synchronous is FULL
        with self._lock:
            self._conn.execute("PRAGMA journal_mode=WAL")
            self._conn.execute("PRAGMA synchronous=NORMAL")
            self._conn.execute(
                "CREATE TABLE IF NOT EXISTS kv (k BLOB PRIMARY KEY, v BLOB NOT NULL)"
            )
            self._conn.commit()

    def get(self, key: bytes) -> bytes | None:
        with self._lock:
            if self._counters is not None:
                self._counters["gets"] += 1
            row = self._conn.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
        return row[0] if row else None

    def _write(self, sets, deletes, sync: bool) -> None:
        """One transaction: sets, then deletes, then the commit — under
        FULL where `sync` (the fsync is the commit's), else NORMAL."""
        with self._lock:
            if sync != self._full:
                # between transactions: the last one was committed
                self._conn.execute(
                    f"PRAGMA synchronous={'FULL' if sync else 'NORMAL'}"
                )
                self._full = sync
            try:
                if sets:
                    self._conn.executemany(
                        "INSERT OR REPLACE INTO kv (k, v) VALUES (?, ?)", sets
                    )
                if deletes:
                    self._conn.executemany(
                        "DELETE FROM kv WHERE k = ?", [(k,) for k in deletes]
                    )
                self._commit(sync)
            except BaseException:
                # all of a batch or none of it: rows of a write that failed
                # half-way must not ride the next commit
                self._conn.rollback()
                raise

    def _commit(self, sync: bool) -> None:
        if sync and self._counters is not None:
            with trace.span("db", "sync", db=self.name):
                self._conn.commit()
            self._counters["sync_commits"] += 1
        else:
            self._conn.commit()

    def _recorded_write(self, sets, deletes, sync: bool) -> None:
        if self._counters is None:
            self._write(sets, deletes, sync)
            return
        n_bytes = sum(len(k) + len(v) for k, v in sets)
        with trace.span(
            "db", "write", db=self.name, rows=len(sets) + len(deletes),
            bytes=n_bytes, sync=sync,
        ):
            self._write(sets, deletes, sync)
        self._counters["bytes_written"] += n_bytes

    def set(self, key: bytes, value: bytes, sync: bool = False) -> None:
        self._recorded_write([(key, value)], (), sync)

    def delete(self, key: bytes) -> None:
        self._recorded_write([], [key], False)

    def iterate(self, start=b"", end=None, reverse=False):
        order = "DESC" if reverse else "ASC"
        if end is None:
            q = f"SELECT k, v FROM kv WHERE k >= ? ORDER BY k {order}"
            args: tuple = (start,)
        else:
            q = f"SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k {order}"
            args = (start, end)
        with self._lock:
            if self._counters is not None:
                self._counters["gets"] += 1
            rows = self._conn.execute(q, args).fetchall()
        for k, v in rows:
            yield bytes(k), bytes(v)

    def write_batch(self, sets, deletes=(), sync: bool = False):
        self._recorded_write(list(sets), deletes, sync)

    def close(self) -> None:
        with self._lock:
            self._conn.close()


@dataclass
class NodeStores:
    """A node's three on-disk stores under one data directory."""

    block_db: SQLiteDB  # blockstore.db: `BlockStore`'s
    state_db: SQLiteDB  # state.db: `StateStore`'s
    app_db: SQLiteDB | None  # app.db: the in-process kvstore app's

    def close(self) -> None:
        for db in (self.block_db, self.state_db, self.app_db):
            if db is not None:
                db.close()


def open_node_stores(data_dir: str, app: bool = True) -> NodeStores:
    """Open (creating where new) the SQLite files a node on disk keeps
    under `data_dir` — the block store's, the state store's and (unless
    the app runs out of process: `app=False`) the kvstore app's — named
    for their spans and counters. Every one of them honours `sync=True`: a
    store that writes through these gives the guarantee its writes ask
    for."""
    os.makedirs(data_dir, exist_ok=True)
    return NodeStores(
        block_db=SQLiteDB(os.path.join(data_dir, "blockstore.db"), "block"),
        state_db=SQLiteDB(os.path.join(data_dir, "state.db"), "state"),
        app_db=SQLiteDB(os.path.join(data_dir, "app.db"), "app") if app else None,
    )
