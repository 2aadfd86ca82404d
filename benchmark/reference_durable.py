"""The plain reference for a full node catching up ONTO DISK (configuration
`durable150`): what a run can show of the guarantee "a block reported applied
is on disk".

After the program has closed its own connections, this opens the node's three
SQLite files with the standard library's `sqlite3`, read-only, in fresh
connections, and reads them as what they are — a table `kv (k BLOB, v BLOB)`
whose values are protobuf messages and one JSON record — with a field reader
of its own. It imports nothing of tendermint_tpu and none of its `DB`
classes; beside the standard library only `reference.py` (the kvstore merkle
root). Its inputs are plain data the driver reads off the seeded fixture:
the heights the run reported applied, the bytes the chain served for each
height, the chain's block hashes and app hashes.

    read_files()   every count below, as a dict of plain numbers
    filesystem()   what `/proc/mounts` says the data directory lies on

What is compared (each a number that must be 0, or a height pair):

  block_rows_missing      for an applied height: no meta row, no hash row
                          that maps the CHAIN's block hash to that height,
                          or a part row short of the meta's count
  block_bytes_mismatches  for an applied height: the stored parts'
                          payloads, concatenated in index order, are not
                          the block bytes the chain served
  store_height, state_height, app_height
                          what the three files say of themselves
  app_hash_mismatch       the app file's pair rows, hashed by the plain
                          kvstore merkle, against the chain's app hash at
                          the app file's own height (and the record's)
  app_rows_off            pair rows other than `txs_per_block` a height,
                          or state records other than one
"""

from __future__ import annotations

import json
import os
import sqlite3

from benchmark.reference import _bytes_field, merkle_root

#: the block store's key layout (store/blockstore.py), as bytes on disk
META, PART, BLOCK_HASH, STORE_STATE = b"H:", b"P:", b"BH:", b"blockStore"
STATE_KEY = b"stateKey"
APP_RECORD, APP_PAIR = b"__kvstore_state__", b"kv:"
#: filesystems that keep nothing across a power cut: an fsync there is free
MEMORY_FILESYSTEMS = ("tmpfs", "ramfs")


def _uvarint(raw: bytes, i: int) -> tuple[int, int]:
    n = shift = 0
    while True:
        b = raw[i]
        i += 1
        n |= (b & 0x7F) << shift
        if not b & 0x80:
            return n, i
        shift += 7


def fields(raw: bytes) -> dict:
    """A protobuf message's top-level fields: {number: [value, ...]}, a
    varint as int, a length-delimited field as bytes."""
    out: dict = {}
    i = 0
    while i < len(raw):
        tag, i = _uvarint(raw, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _uvarint(raw, i)
        elif wire == 2:
            n, i = _uvarint(raw, i)
            value, i = raw[i:i + n], i + n
            if len(value) != n:
                raise ValueError("truncated field")
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value, i = raw[i:i + n], i + n
        else:
            raise ValueError(f"wire type {wire}")
        out.setdefault(number, []).append(value)
    return out


def served_block_bytes(wire: bytes) -> bytes:
    """The block inside one served block-sync response: the message is one
    length-delimited field whose payload is the block."""
    (payloads,) = fields(wire).values()
    (block,) = payloads
    return block


def _connect(path: str) -> sqlite3.Connection:
    return sqlite3.connect(f"file:{path}?mode=ro", uri=True)


def _get(conn: sqlite3.Connection, key: bytes) -> bytes | None:
    row = conn.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()
    return bytes(row[0]) if row else None


def _height_key(prefix: bytes, height: int) -> bytes:
    return prefix + height.to_bytes(8, "big")


def read_block_file(path: str, applied: list, served: dict, block_hash_at: dict) -> dict:
    """`served`: height -> the block bytes the chain served for it."""
    conn = _connect(path)
    try:
        missing = mismatched = 0
        for h in applied:
            meta = _get(conn, _height_key(META, h))
            at = _get(conn, BLOCK_HASH + block_hash_at[h])
            rows = conn.execute(
                "SELECT v FROM kv WHERE k >= ? AND k < ? ORDER BY k",
                (_height_key(PART, h), _height_key(PART, h + 1))).fetchall()
            try:
                # meta {1: BlockID {1: hash, 2: PartSetHeader {1: total}}}
                block_id = fields(fields(meta)[1][0])
                total = fields(block_id[2][0])[1][0]
                ok = (block_id[1][0] == block_hash_at[h]
                      and at == h.to_bytes(8, "big") and len(rows) == total)
                # part {1: index + 1, 2: payload, 3: proof}
                payload = b"".join(fields(bytes(v))[2][0] for (v,) in rows)
            except (TypeError, KeyError, IndexError, ValueError):
                ok, payload = False, None
            if not ok:
                missing += 1
            elif payload != served[h]:
                mismatched += 1
        state = _get(conn, STORE_STATE)
        height = fields(state).get(2, [0])[0] if state else 0
        return {"block_rows_missing": missing, "block_bytes_mismatches": mismatched,
                "store_height": height}
    finally:
        conn.close()


def read_state_file(path: str) -> dict:
    """The saved state's height and app hash (state {3: last_block_height,
    13: app_hash})."""
    conn = _connect(path)
    try:
        raw = _get(conn, STATE_KEY)
        f = fields(raw) if raw else {}
        return {"state_height": f.get(3, [0])[0], "state_app_hash": f.get(13, [b""])[0]}
    finally:
        conn.close()


def read_app_file(path: str, app_hash_at: dict, txs_per_block: int) -> dict:
    conn = _connect(path)
    try:
        records = conn.execute("SELECT v FROM kv WHERE k = ?", (APP_RECORD,)).fetchall()
        record = json.loads(bytes(records[0][0])) if records else {}
        height = int(record.get("height", 0))
        pairs = [(bytes(k)[len(APP_PAIR):], bytes(v)) for k, v in conn.execute(
            "SELECT k, v FROM kv WHERE k >= ? AND k < ? ORDER BY k",
            (APP_PAIR, APP_PAIR[:-1] + bytes([APP_PAIR[-1] + 1])))]
        root = merkle_root([_bytes_field(1, k) + _bytes_field(2, v) for k, v in sorted(pairs)])
        want = app_hash_at.get(height, b"")
        return {
            "app_height": height,
            "app_hash_mismatch": int(root != want) + int(
                bytes.fromhex(record.get("app_hash", "")) != want),
            "app_rows_off": abs(len(pairs) - txs_per_block * height) + abs(len(records) - 1)
            + abs(int(record.get("size", -1)) - len(pairs)),
        }
    finally:
        conn.close()


def read_files(data_dir: str, applied: list, served: dict, block_hash_at: dict,
               app_hash_at: dict, txs_per_block: int) -> dict:
    out = read_block_file(os.path.join(data_dir, "blockstore.db"), applied, served,
                          block_hash_at)
    out.update(read_state_file(os.path.join(data_dir, "state.db")))
    out.update(read_app_file(os.path.join(data_dir, "app.db"), app_hash_at, txs_per_block))
    return out


def filesystem(path: str, mounts: str = "/proc/mounts") -> tuple[str, str]:
    """(type, mount point) of the filesystem `path` lies on: the longest
    mount point that is a prefix of it, the last such line winning."""
    path = os.path.realpath(path)
    best = ("unknown", "")
    with open(mounts) as f:
        for line in f:
            parts = line.split()
            if len(parts) < 3:
                continue
            mount, kind = parts[1], parts[2]
            under = path == mount or path.startswith(mount.rstrip("/") + "/")
            if under and len(mount) >= len(best[1]):
                best = (kind, mount)
    return best


def on_disk(kind: str) -> bool:
    return kind not in MEMORY_FILESYSTEMS and kind != "unknown"
