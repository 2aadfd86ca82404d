"""The node on disk (`durable150.blocksync`): its own files at a tiny size on
the CPU — the plain reference's readers on files made by hand (a proto field
reader of its own, the block store's rows, the app's), what `/proc/mounts`
says of a path, the `durable_readers` arithmetic on readings made by hand,
with a program that records `db.write` / `db.sync` and counts per DB and with
one that does not (nothing to read, never a raise), and the data directory's
sweep. Tier-1's `tests/test_durable150.py` drives the cell itself."""

import json
import os
import sqlite3
from types import SimpleNamespace

import pytest

from benchmark import durable_readers as dr
from benchmark import harness
from benchmark import program_spans as ps
from benchmark import reference_durable as refd
from benchmark.reference import kv_state_hash


# -- the reference's own readers ---------------------------------------------------------


def _msg(*fields):
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += bytes([number << 3]) + _varint(value)
        else:
            out += bytes([(number << 3) | 2]) + _varint(len(value)) + value
    return out


def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def test_fields_reads_varints_and_bytes_and_refuses_a_short_field():
    raw = _msg((1, 300), (2, b"abc"), (2, b""), (13, b"\x01" * 32))
    assert refd.fields(raw) == {1: [300], 2: [b"abc", b""], 13: [b"\x01" * 32]}
    assert refd.served_block_bytes(_msg((3, b"the block"))) == b"the block"
    with pytest.raises(ValueError):
        refd.fields(raw[:-3])


def _kv_file(path, rows):
    conn = sqlite3.connect(path)
    conn.execute("CREATE TABLE kv (k BLOB PRIMARY KEY, v BLOB NOT NULL)")
    conn.executemany("INSERT INTO kv VALUES (?, ?)", rows)
    conn.commit()
    conn.close()


def _block_rows(h, block_hash, payload, parts=2):
    cut = len(payload) // parts
    pieces = [payload[:cut], payload[cut:]] if parts == 2 else [payload]
    meta = _msg((1, _msg((1, block_hash), (2, _msg((1, parts), (2, b"p" * 32))))), (2, len(payload)))
    rows = [(refd.META + h.to_bytes(8, "big"), meta),
            (refd.BLOCK_HASH + block_hash, h.to_bytes(8, "big"))]
    rows += [(refd.PART + h.to_bytes(8, "big") + i.to_bytes(4, "big"),
              _msg((1, i + 1), (2, piece), (3, b"proof")))
             for i, piece in enumerate(pieces)]
    return rows


def test_block_file_reader_counts_what_is_missing_and_what_differs(tmp_path):
    hashes = {h: bytes([h]) * 32 for h in (1, 2, 3, 4)}
    served = {h: b"block-%d-" % h * 20 for h in hashes}
    rows = _block_rows(1, hashes[1], served[1]) + _block_rows(2, hashes[2], served[2])
    rows += _block_rows(3, hashes[3], served[3][:-1] + b"X")  # one byte off
    rows += [r for r in _block_rows(4, hashes[4], served[4]) if r[0][:2] != refd.PART][:2]
    rows.append((refd.STORE_STATE, _msg((1, 1), (2, 4))))
    path = str(tmp_path / "blockstore.db")
    _kv_file(path, rows)
    assert refd.read_block_file(path, [1, 2], served, hashes) == {
        "block_rows_missing": 0, "block_bytes_mismatches": 0, "store_height": 4}
    got = refd.read_block_file(path, [1, 2, 3, 4], served, hashes)
    assert got["block_bytes_mismatches"] == 1 and got["block_rows_missing"] == 1
    # a hash row under ANOTHER hash than the chain's does not count
    wrong = {**hashes, 2: b"\xff" * 32}
    assert refd.read_block_file(path, [2], served, wrong)["block_rows_missing"] == 1


def test_state_and_app_file_readers(tmp_path):
    state = str(tmp_path / "state.db")
    _kv_file(state, [(refd.STATE_KEY, _msg((1, b"chain"), (3, 77), (13, b"\xaa" * 32)))])
    assert refd.read_state_file(state) == {"state_height": 77, "state_app_hash": b"\xaa" * 32}
    txs = [b"k%d=v%d" % (i, i) for i in range(6)]
    want = kv_state_hash(txs)
    rows = [(refd.APP_PAIR + b"k%d" % i, b"v%d" % i) for i in range(6)]
    rows.append((b"val:" + b"\x01" * 32, b"10"))  # a validator row is not a pair
    record = {"height": 3, "app_hash": want.hex(), "size": 6}
    app = str(tmp_path / "app.db")
    _kv_file(app, rows + [(refd.APP_RECORD, json.dumps(record).encode())])
    assert refd.read_app_file(app, {3: want}, 2) == {
        "app_height": 3, "app_hash_mismatch": 0, "app_rows_off": 0}
    got = refd.read_app_file(app, {3: b"\x00" * 32}, 2)
    assert got["app_hash_mismatch"] == 2 and got["app_rows_off"] == 0
    assert refd.read_app_file(app, {3: want}, 3)["app_rows_off"] == 3
    empty = str(tmp_path / "empty.db")
    _kv_file(empty, [])
    assert refd.read_app_file(empty, {}, 2)["app_rows_off"] >= 1


def test_filesystem_takes_the_longest_mount_and_memory_is_not_disk(tmp_path):
    mounts = tmp_path / "mounts"
    mounts.write_text(
        "none / 9p rw 0 0\n"
        "tmpfs /dev/shm tmpfs rw 0 0\n"
        f"/dev/vdb {tmp_path}/data ext4 rw 0 0\n"
        f"tmpfs {tmp_path}/data/ram ramfs rw 0 0\n")
    for sub in ("data/ram", "data/disk"):
        os.makedirs(tmp_path / sub)
    at = lambda p: refd.filesystem(str(p), str(mounts))  # noqa: E731
    assert at(tmp_path / "data" / "disk") == ("ext4", f"{tmp_path}/data")
    assert at(tmp_path / "data" / "ram") == ("ramfs", f"{tmp_path}/data/ram")
    assert at(tmp_path) == ("9p", "/") and at("/dev/shm") == ("tmpfs", "/dev/shm")
    assert refd.on_disk("ext4") and refd.on_disk("9p")
    assert not refd.on_disk("tmpfs") and not refd.on_disk("ramfs") and not refd.on_disk("unknown")
    kind, mount = refd.filesystem(harness.ROOT)  # the real table parses too
    assert kind != "unknown" and harness.ROOT.startswith(mount)


# -- the readers --------------------------------------------------------------------------


def _recorded(monkeypatch, rows):
    def window_rows(t0, t1):
        return [d for d in rows if d["end"] > t0 and d["start"] < t1]

    monkeypatch.setattr(ps, "window_rows", window_rows)
    return SimpleNamespace(t0=10.0, t1=20.0, units=100)


def _row(key, start, end, **attrs):
    sub, name = key.split(".", 1)
    return {"subsystem": sub, "name": name, "start": start, "end": end, "attrs": attrs}


def test_readers_on_a_window_made_by_hand(monkeypatch):
    rows = []
    for i in range(8):  # eight heights inside the window, one after it
        t = 10.5 + i
        rows += [
            _row("db.write", t, t + 0.010, db="block", rows=6, bytes=48000, sync=True),
            _row("db.sync", t + 0.004, t + 0.010, db="block"),
            _row("db.write", t + 0.02, t + 0.024, db="app", rows=3, bytes=200 + i, sync=False),
        ]
    rows.append(_row("db.sync", 21.0, 21.5, db="state"))
    r = _recorded(monkeypatch, rows)
    assert dr.sync_ms_per_unit(r) == pytest.approx(1e3 * 8 * 0.006 / 100)
    assert dr.write_ms_per_unit(r) == pytest.approx(1e3 * 8 * (0.010 + 0.004 - 0.006) / 100)
    assert dr.app_bytes_per_commit(r, 0.0, 0.25) == pytest.approx(200.5)
    assert dr.app_bytes_per_commit(r, 0.75, 1.0) == pytest.approx(206.5)
    spans_only = {"db_sync_ms_per_block", "db_write_ms_per_block",
                  "app_db_bytes_per_block_first_quarter", "app_db_bytes_per_block_last_quarter"}
    assert set(dr.report(10.0, 20.0, 100)) == spans_only
    # the counters are the driver's note of THIS window, by its clock
    assert dr.counter_per_unit(r, "bytes_written") is None
    dr.note_window(10.0, 20.0, {"block.bytes_written": 4800000.0, "state.bytes_written": 3000000.0,
                                "app.bytes_written": 20000.0, "block.sync_commits": 100.0,
                                "state.sync_commits": 200.0, "app.gets": 0.0})
    assert dr.counter_per_unit(r, "bytes_written") == 78200.0
    assert dr.counter_per_unit(r, "bytes_written", "app") == 200.0
    assert dr.counter_per_unit(r, "sync_commits") == 3.0
    assert dr.counter_per_unit(r, "gets") == 0.0
    assert dr.counter_per_unit(r, "no_such_counter") is None
    assert dr.counter_per_unit(SimpleNamespace(t0=1.0, t1=2.0, units=5), "gets") is None
    assert set(dr.report(10.0, 20.0, 100)) == spans_only | {
        "db_synced_commits_per_block", "db_bytes_per_block", "db_gets_per_block",
        "app_db_bytes_per_block"}


def test_readers_find_nothing_on_a_program_without_the_spans(monkeypatch):
    r = _recorded(monkeypatch, [_row("state.save", 10.0, 10.5), _row("blocksync.apply", 10, 11)])
    assert dr.sync_ms_per_unit(r) is None and dr.write_ms_per_unit(r) is None
    assert dr.app_bytes_per_commit(r, 0.0, 0.25) is None
    assert dr.report(11.0, 19.0, 100) == {}
    monkeypatch.setattr(ps, "window_rows", lambda t0, t1: None)  # recorder off / ring wrapped
    assert dr.app_bytes_per_commit(r, 0.0, 1.0) is None and dr.report(11.0, 19.0, 1) == {}


def test_the_two_durable_files_take_their_arithmetic_from_one_module():
    """The harness's own tests count the metric files that name the recorder's
    reader and the on-CPU one by name: the `.durable` files name neither."""
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".durable")]
    assert [m["name"] for m in mine] == ["db_sync_ms_per_block.durable",
                                         "db_bytes_per_block.durable"]
    assert len(bench["per_layer"]) == 128  # the contract's most: why there are two, not nineteen
    for m in mine:
        assert m["workloads"] == ["durable150.blocksync"]
        text = open(os.path.join(harness.ROOT, "benchmark", "metrics", m["name"] + ".py")).read()
        assert "program_spans" not in text and "cpu_readers" not in text
        assert "from benchmark import durable_readers" in text


# -- the data directory --------------------------------------------------------------------


def test_a_dead_run_s_directory_is_swept_and_a_live_one_s_is_kept(monkeypatch, tmp_path):
    from benchmark.drivers import blocksync_durable as driver

    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    base = tmp_path / driver.DATA_ROOT
    dead, live, other = (base / n for n in (
        "acell.blocksync-999999999", f"acell.blocksync-{os.getppid()}", "bcell.blocksync-999999999"))
    for d in (dead, live, other):
        os.makedirs(d / "node")
    data = driver.make_data_dir("acell.blocksync", 1 << 20)
    assert data.root == str(base / f"acell.blocksync-{os.getpid()}") and os.path.isdir(data.root)
    assert not dead.exists() and live.exists() and other.exists()
    assert data.on_disk == refd.on_disk(data.fs.split()[0]) and data.free_bytes > 0
    with pytest.raises(RuntimeError, match="bytes free"):
        driver.make_data_dir("acell.blocksync", 1 << 60)
    assert driver.dir_bytes(str(base)) == 0 and driver.fsync_ms(data.root, n=3) >= 0
