"""Readers of what a node ON DISK adds to a block-sync window (`*.durable`,
cell `durable150.blocksync`): the program's `db.write` [db, rows, bytes,
sync] span around every write of a named `SQLiteDB`, the `db.sync` span
inside it around a synced `commit()` alone (the WAL frames' write, the fsync
and every ≈ 1,000 pages a checkpoint: the commit's price under FULL), and its
per-DB counters `store.db.COUNTERS` (`sync_commits`, `bytes_written`,
`gets`), which the harness's own counter reading does not hold: the driver
notes their deltas over the window here (`note_window`), in this process.

A program without the spans (the parent of the PR that added them: it cannot
run the cell at all), a recorder that is off, a ring that wrapped, or a
window nobody noted, leaves a reader with nothing to read: None, never a
raise and never 0.
"""

from __future__ import annotations

from types import SimpleNamespace

from benchmark import program_spans as ps

WRITE, SYNC = "db.write", "db.sync"

#: (t0, t1) of the window the driver noted -> its counter deltas
_windows: dict = {}


def note_window(t0: float, t1: float, counters: dict) -> None:
    _windows[(round(t0, 6), round(t1, 6))] = dict(counters)


def _counters(r) -> dict | None:
    return _windows.get((round(r.t0, 6), round(r.t1, 6)))


def counter_per_unit(r, key: str, db: str | None = None):
    """Δ of the per-DB counter `key` over the window — summed over the DBs,
    or one DB's — per block applied."""
    c = _counters(r)
    if c is None or not r.units:
        return None
    mine = [v for name, v in c.items()
            if name.endswith("." + key) and (db is None or name == f"{db}.{key}")]
    return sum(mine) / r.units if mine else None


def sync_ms_per_unit(r):
    """ms inside `db.sync` per block applied: the synced commits."""
    return ps.ms_per_unit(r, SYNC)


def write_ms_per_unit(r):
    """ms inside `db.write` per block applied WITHOUT the synced commits'
    `db.sync`: the SQL, the page cache, the unsynced commits."""
    return ps.ms_per_unit_less(r, WRITE, SYNC)


def app_bytes_per_commit(r, lo: float, hi: float):
    """Bytes the app's file was handed per commit of the app (one `db.write`
    [db=app] a block) over the part [lo, hi) of the window's commits, 0 the
    first and 1 the last: flat along the chain where the app writes what a
    block changed, growing with the height where it writes its whole state."""
    rows = ps.window_rows(r.t0, r.t1)
    if rows is None:
        return None
    mine = sorted((d for d in ps.select(rows, WRITE)
                   if (d.get("attrs") or {}).get("db") == "app"
                   and r.t0 <= d["start"] < r.t1), key=lambda d: d["start"])
    mine = mine[int(lo * len(mine)):int(hi * len(mine))]
    if not mine:
        return None
    return sum(float(d["attrs"].get("bytes", 0)) for d in mine) / len(mine)


def report(t0: float, t1: float, units: int) -> dict:
    """Every reading above for a result line's own keys, in any run (the
    window noted first): what its counters and its spans say of one block."""
    r = SimpleNamespace(t0=t0, t1=t1, units=units)
    out = {"db_synced_commits_per_block": counter_per_unit(r, "sync_commits"),
           "db_bytes_per_block": counter_per_unit(r, "bytes_written"),
           "db_gets_per_block": counter_per_unit(r, "gets"),
           "app_db_bytes_per_block": counter_per_unit(r, "bytes_written", "app"),
           "db_sync_ms_per_block": sync_ms_per_unit(r),
           "db_write_ms_per_block": write_ms_per_unit(r),
           "app_db_bytes_per_block_first_quarter": app_bytes_per_commit(r, 0.0, 0.25),
           "app_db_bytes_per_block_last_quarter": app_bytes_per_commit(r, 0.75, 1.0)}
    return {k: v for k, v in out.items() if v is not None}
