"""The node ON DISK as a deployment (cell `durable150.blocksync`), at a small
size: the benchmark's own `blocksync_durable` driver drives a seeded chain of
10 validators through the real `BlockSyncReactor`, hub, executor and stores
onto three SQLite files, and every number compared equals the plain
reference's (`benchmark/reference_durable.py`: the files read back through
plain `sqlite3` after the program has closed them, then the handshake over
them). The same run with a row deleted or a part truncated between the
program's close and the reference's read, or with one of a height's three
commits left unsynced, has to come out not `correct`, each by the checks
that are there for it.
"""

import json
import os
import sqlite3

import pytest

from benchmark import reference_durable as refd
from benchmark import run
from benchmark.drivers import blocksync_durable as driver
from benchmark.tests import tiny_durable
from tendermint_tpu.libs import trace
from tendermint_tpu.state.store import StateStore

#: what the host route cannot show: no device
HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs"}
DURABLE_CHECKS = {
    "block_rows_missing", "block_bytes_mismatches", "state_height_behind_store",
    "applied_ahead_of_state", "app_height_off", "app_hash_mismatch.files", "app_rows_off",
    "block_sync_commits_short", "state_sync_commits_short", "data_fs_on_disk",
    "handshake_replayed_blocks", "handshake_faults"}
SECONDS = 1.5


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_durable.make_root(str(tmp_path_factory.mktemp("durable")))


@pytest.fixture
def benchmark_ring():
    """The recorder's ring as it was, after a run whose driver widened it."""
    old = trace.RECORDER.ring_size
    yield
    trace.configure(ring_size=old)


def _failed(res):
    return {k for k, c in res["checks"].items() if not c["ok"]}


def _run(root, seed, traced=False):
    return run.execute(root, tiny_durable.CELL, seed, SECONDS, traced,
                       device=tiny_durable.CPU_DEVICE)


@pytest.mark.parametrize("seed", [3000004311, 3000004312])
def test_sound_run_holds_every_check_but_the_device_s(root, benchmark_ring, seed):
    res = _run(root, seed)
    assert _failed(res) == HOST_ROUTE_CHECKS and res["correct"] is False
    checks = {k: c["value"] for k, c in res["checks"].items()}
    assert DURABLE_CHECKS <= set(checks)
    applied = checks["blocks_applied"]
    assert applied > 0 and res["chain_left_blocks"] > 64
    assert res["metrics"]["blocksync_blocks_per_s"]["value"] > 0
    # the result line's own keys: where the files lay, how large they grew,
    # and what a block cost the three of them
    assert refd.on_disk(res["data_fs"].split()[0]) and res["data_bytes"] > applied * 4096
    assert res["fsync_ms"] > 0 and res["data_free_bytes"] > res["data_bytes"]
    assert res["db_synced_commits_per_block"] == 3.0  # block, responses, state
    assert res["db_gets_per_block"] == 0.0  # apply reads nothing back
    assert 0 < res["app_db_bytes_per_block"] < 400 < res["db_bytes_per_block"]
    assert res["db_sync_ms_per_block"] > 0 and res["db_write_ms_per_block"] > 0
    # the app writes what a block changed: flat along the chain
    first, last = (res[f"app_db_bytes_per_block_{q}_quarter"] for q in ("first", "last"))
    assert abs(last - first) <= 0.1 * first
    assert res["handshake_replayed_blocks"] <= 1
    assert isinstance(res["cut_inside_apply"], bool)
    # the run's directory is gone
    assert not os.path.exists(os.path.join(
        driver.harness.ROOT, driver.DATA_ROOT, f"{tiny_durable.CELL}-{os.getpid()}"))


def test_traced_run_reports_exactly_the_cell_s_metrics(root, benchmark_ring):
    res = _run(root, 3000004313, traced=True)
    assert _failed(res) == HOST_ROUTE_CHECKS
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # what a CPU run on the host route can read: spans and counters (no
    # device plane and no tpu.* span: those readers are left out, never 0)
    assert set(m) == {
        "db_sync_ms_per_block.durable", "db_bytes_per_block.durable",
        "verify_ms_per_block.blocksync", "apply_ms_per_block.blocksync",
        "exec_ms_per_block.blocksync", "store_ms_per_block.blocksync",
        "build_ms_per_block.blocksync", "hub_sigs_per_dispatch.blocksync",
        "device_route_share.blocksync", "inline_compiles.blocksync"}
    assert m["db_sync_ms_per_block.durable"] == pytest.approx(res["db_sync_ms_per_block"])
    assert m["db_bytes_per_block.durable"] == pytest.approx(res["db_bytes_per_block"])
    # the fsyncs sit inside the stores' spans, which sit inside apply
    assert 0 < m["db_sync_ms_per_block.durable"] < m["store_ms_per_block.blocksync"]
    assert m["store_ms_per_block.blocksync"] < m["apply_ms_per_block.blocksync"]
    assert trace.RECORDER.dropped == 0 or trace.RECORDER.ring_size == driver.RING_ROWS


def test_the_benchmark_lists_the_cell_where_its_readers_find_something():
    bench = json.load(open(os.path.join(driver.harness.ROOT, "BENCHMARK.json")))
    cell = "durable150.blocksync"
    mine = {m["name"] for m in bench["per_layer"] if cell in m.get("workloads", ())}
    assert {n for n in mine if n.endswith(".durable")} == {
        "db_sync_ms_per_block.durable", "db_bytes_per_block.durable"}
    assert {"setup_probe_s", "setup_selftest_s", "kernel_eq_roofline.blocksync",
            "device_idle_share.blocksync", "kernel_ms_per_ksig.blocksync",
            "device_wait_ms_per_dispatch.blocksync"} <= mine
    assert all(m["moves"] in ("blocksync_blocks_per_s", "setup_s")
               for m in bench["per_layer"] if m["name"] in mine)
    cfg = next(c for c in bench["configs"] if c["name"] == "durable150")
    assert "stores" not in cfg["reduced"] and cfg["reduced"] == ["chain_length", "tx_index"]
    for other in ("full150", "churn150", "mixedfull150"):  # theirs stay as they were
        assert "stores" in next(c for c in bench["configs"] if c["name"] == other)["reduced"]
    full = json.load(open(os.path.join(driver.harness.ROOT, "benchmark", "workloads",
                                       "full150.blocksync.json")))["traffic"]
    ours = json.load(open(os.path.join(driver.harness.ROOT, "benchmark", "workloads",
                                       f"{cell}.json")))["traffic"]
    assert {k: ours[k] for k in full} == full  # full150.blocksync's traffic to the letter
    assert set(ours) - set(full) == {"stores", "synced_commits_per_height"}


# -- damage between the program's close and the reference's read ------------------------


def _sql(data_dir, name, statement, args=()):
    conn = sqlite3.connect(os.path.join(data_dir, name))
    try:
        conn.execute(statement, args)
        conn.commit()
    finally:
        conn.close()


def _delete_a_part(data_dir):
    _sql(data_dir, "blockstore.db", "DELETE FROM kv WHERE k = ?",
         (refd.PART + (20).to_bytes(8, "big") + (0).to_bytes(4, "big"),))


def _truncate_a_part(data_dir):
    _sql(data_dir, "blockstore.db", "UPDATE kv SET v = substr(v, 1, length(v) - 7) WHERE k = ?",
         (refd.PART + (21).to_bytes(8, "big") + (0).to_bytes(4, "big"),))


def _flip_a_part(data_dir):
    """One byte of a part's payload changed, its length kept."""
    key = refd.PART + (22).to_bytes(8, "big") + (0).to_bytes(4, "big")
    conn = sqlite3.connect(os.path.join(data_dir, "blockstore.db"))
    raw = bytearray(conn.execute("SELECT v FROM kv WHERE k = ?", (key,)).fetchone()[0])
    raw[40] ^= 1
    conn.execute("UPDATE kv SET v = ? WHERE k = ?", (bytes(raw), key))
    conn.commit()
    conn.close()


def _delete_an_app_row(data_dir):
    conn = sqlite3.connect(os.path.join(data_dir, "app.db"))
    key = conn.execute("SELECT k FROM kv WHERE k >= ? ORDER BY k LIMIT 1", (b"kv:",)).fetchone()[0]
    conn.execute("DELETE FROM kv WHERE k = ?", (key,))
    conn.commit()
    conn.close()


def _roll_the_state_back(data_dir):
    """The state file as it stood some heights ago: an unsynced state."""
    conn = sqlite3.connect(os.path.join(data_dir, "state.db"))
    raw = bytes(conn.execute("SELECT v FROM kv WHERE k = ?", (refd.STATE_KEY,)).fetchone()[0])
    f = refd.fields(raw)
    height = f[3][0]
    assert height > 5

    def enc(n):  # a varint, whatever its length
        out = bytearray()
        while True:
            b, n = n & 0x7F, n >> 7
            out.append(b | 0x80 if n else b)
            if not n:
                return bytes(out)

    at = raw.index(b"\x18" + enc(height))  # field 3, varint: right after the chain ID
    conn.execute("UPDATE kv SET v = ? WHERE k = ?",
                 (raw[:at] + b"\x18" + enc(height - 5) + raw[at + 1 + len(enc(height)):],
                  refd.STATE_KEY))
    conn.commit()
    conn.close()


@pytest.mark.parametrize("damage,failing", [
    (_delete_a_part, {"block_rows_missing"}),
    (_truncate_a_part, {"block_rows_missing"}),
    (_flip_a_part, {"block_bytes_mismatches"}),
    (_delete_an_app_row, {"app_hash_mismatch.files", "app_rows_off"}),
    (_roll_the_state_back, {"state_height_behind_store", "applied_ahead_of_state",
                            "app_height_off"}),
], ids=lambda x: x.__name__.strip("_") if callable(x) else None)
def test_damaged_files_are_not_correct(root, benchmark_ring, monkeypatch, damage, failing):
    monkeypatch.setattr(driver, "files_closed", damage)
    res = _run(root, 3000004314)
    # the handshake over damaged files may fail in its own ways besides
    assert failing <= _failed(res) - HOST_ROUTE_CHECKS <= failing | {
        "handshake_faults", "handshake_replayed_blocks"}
    assert res["correct"] is False


def test_a_sync_counter_under_three_a_height_is_not_correct(root, benchmark_ring, monkeypatch):
    """The responses written as the parent writes them: unsynced."""
    def unsynced(self, height, responses):
        self.db.set(b"abciResponsesKey:" + height.to_bytes(8, "big"), responses.encode())

    monkeypatch.setattr(StateStore, "save_abci_responses", unsynced)
    res = _run(root, 3000004315)
    assert _failed(res) - HOST_ROUTE_CHECKS == {"state_sync_commits_short"}
    assert res["db_synced_commits_per_block"] == 2.0


def test_a_program_without_the_synced_store_api_fails_before_anything_else(monkeypatch):
    """What the parent commit does with this driver: the import raises."""
    import importlib
    import sys

    from tendermint_tpu.store import db

    monkeypatch.delattr(db, "open_node_stores")
    monkeypatch.delitem(sys.modules, "benchmark.drivers.blocksync_durable")
    with pytest.raises(ImportError):
        importlib.import_module("benchmark.drivers.blocksync_durable")
    monkeypatch.undo()
    # ... and a DB whose writes cannot ask for a sync is refused by `build`
    monkeypatch.setattr(db.DB, "set", lambda self, key, value: None)
    with pytest.raises(RuntimeError, match="takes no `sync`"):
        next(driver.build({}, {}, 1))


# -- the spans a height on disk leaves -----------------------------------------------------


@pytest.mark.asyncio
async def test_a_height_on_disk_puts_its_writes_under_the_stores_spans(tmp_path):
    from benchmark import fixtures, harness
    from benchmark.drivers import blocksync as bs_driver
    from tendermint_tpu.crypto import verify_hub as vh

    chain = await fixtures.kvstore_chain(3000004316, "durtree", 70, 10, 10, 2)
    cell = {"traffic": {"peers": 4, "window": 64, "trace_seconds": 0.1}}
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    hub = vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=8192)
    try:
        with driver.node_on_disk(str(tmp_path / "node")):
            trace.RECORDER.clear()
            s = await bs_driver._sync(chain, cell, 60.0, harness.Spans())
        spans = trace.RECORDER.dump()
    finally:
        vh.release_hub()
        trace.RECORDER.enabled = old
        trace.RECORDER.clear()
    assert hub is not None and s.final_height >= 64
    key = lambda x: f"{x['subsystem']}.{x['name']}"  # noqa: E731
    ids = {x["span_id"]: x for x in spans}
    root = next(x for x in spans if key(x) == "blocksync.range")
    mine = [x for x in spans if x["trace_id"] == root["trace_id"]]
    writes = [x for x in mine if key(x) == "db.write"]
    syncs = [x for x in mine if key(x) == "db.sync"]
    n = root["attrs"]["n"]
    assert len(writes) == 4 * n and len(syncs) == 3 * n
    under = {"block": "blocksync.save_block", "app": "state.commit"}
    for x in writes:
        parent = key(ids[x["parent_id"]])
        db = x["attrs"]["db"]
        assert parent == under.get(db, parent) and x["attrs"]["sync"] is (db != "app")
        if db == "state":
            assert parent in ("state.save_responses", "state.save")
    for x in syncs:
        assert key(ids[x["parent_id"]]) == "db.write"
        assert ids[x["parent_id"]]["attrs"]["db"] == x["attrs"]["db"]
