"""Driver `blocksync_durable`: `blocksync`'s full node catching up ONTO DISK
(configuration `durable150`).

Fixture, install, the window's loop, the peer stand-ins and the hub are
`blocksync`'s, unchanged, and so is every check it makes. What differs is the
node: its block store, state store and kvstore app sit on SQLite files under
a data directory, opened by the program's own `store.db.open_node_stores` —
the API a node on disk starts through (`cli.py`) and the one through which a
write can ask to survive a crash (`sync=True`). `blocksync._sync` builds its
node with `fixtures.fresh_node`; while one of this driver's syncs runs, that
name gives the same node over the data directory (`node_on_disk`). Nothing
else of the run is touched.

A program without the synced-store API cannot give the configuration's
guarantee; this module asks for it when it is imported (`run.py` imports the
driver before it starts the fixture child or attaches the device) and again
as `build`'s first statement, and such a program raises there.

The data directory is made per run under the checkout's own filesystem,
`<checkout>/.bench_data/<cell>-<pid>/` (git-ignored; `warm/` for the warm-up
sync, `node/` for the window's), and removed when the comparison is over.
Where the checkout lies on `tmpfs`/`ramfs` it falls back to the process's
temporary directory if THAT is a disk, and otherwise stays — `data_fs` in the
result line says which, and the `data_fs_on_disk` check fails: fsyncs to
memory are free, and such a run would measure SQL, not disk.

Beside `blocksync`'s checks, from `reference_durable.py` (plain `sqlite3`,
read-only, fresh connections after the program has closed its own):

  block_rows_missing, block_bytes_mismatches
        every height the run reported applied is in the block file: its
        meta and hash rows under the chain's block hash, its parts — whose
        payloads, concatenated, are the bytes the chain served
  state_height_behind_store, applied_ahead_of_state
        the state file is at the block file's height, or one behind it only
        where the window was cut inside an apply (the result line's
        `cut_inside_apply` says which); never behind a height reported applied
  app_height_off, app_hash_mismatch, app_rows_off
        the app file is at the state's height (one ahead where the cut fell
        between its commit and the state's save), its pair rows hash to the
        chain's app hash at that height by the reference's plain merkle,
        2 rows a height and one record
  block_sync_commits_short, state_sync_commits_short
        the program's own counters (`store.db.COUNTERS`) over the measured
        node's life: a synced commit of the block file for every applied
        height, two of the state file (responses, state) — three a height
  data_fs_on_disk
        `/proc/mounts` names a filesystem that is not memory
  handshake_replayed_blocks, handshake_faults
        the second pass, through the NORMAL path: new stores over the same
        files, a fresh app over the app file, `Handshaker.handshake` — it
        replays at most one block and arrives at the chain's app hash with
        app, state and block store at one height
"""

from __future__ import annotations

import asyncio
import contextlib
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field

from benchmark import durable_readers, fixtures, harness
from benchmark import reference_durable as refd
from benchmark.drivers import blocksync as base
from benchmark.harness import Check, say

# the synced-store API: a program that lacks it ends the run HERE, before
# the fixture child is started and before the device is attached
from tendermint_tpu.store.db import open_node_stores, COUNTERS  # isort: skip

END_TO_END = base.END_TO_END
FIXTURE = base.FIXTURE
release = base.release

DATA_ROOT = ".bench_data"
#: rows the flight recorder's ring holds in this driver's runs: a height on
#: disk leaves 7 more rows than on MemDB (4 `db.write`, 3 `db.sync`), and a
#: 20 s window with its 5 s stretch at 70 blocks/s would overrun the
#: default 32,768 — a ring that wrapped inside the window is refused by
#: every span reader
RING_ROWS = 65536


def files_closed(data_dir: str) -> None:
    """Called once the program has closed the window's files, before the
    reference opens them. Nothing to do: the driver's own test puts its
    damage here (a deleted row, a truncated part)."""


def _require_synced_stores() -> None:
    import inspect

    from tendermint_tpu.store.db import DB

    for method in (DB.set, DB.write_batch):
        if "sync" not in inspect.signature(method).parameters:
            raise RuntimeError(f"store.db.DB.{method.__name__} takes no `sync`: this program "
                               "cannot state the configuration's guarantee")


def build(cfg: dict, cell: dict, seed: int):
    _require_synced_stores()
    p = cell["traffic"]
    if p["stores"] != "sqlite-wal" or p["synced_commits_per_height"] != 3:
        raise RuntimeError(f"cell states stores {p['stores']!r} with "
                           f"{p['synced_commits_per_height']} synced commits a height; this "
                           "driver runs sqlite-wal with 3")
    yield from base.build(cfg, cell, seed)


def install(patches: harness.Patches, spans: harness.Spans, traced: bool) -> None:
    from tendermint_tpu.libs import trace

    base.install(patches, spans, traced)
    if trace.RECORDER.ring_size < RING_ROWS:
        trace.configure(ring_size=RING_ROWS)


# -- the data directory --------------------------------------------------------------


@dataclass
class DataDir:
    root: str  # this run's directory
    fs: str  # the result line's `data_fs`
    on_disk: bool
    free_bytes: int


def _alive(pid: str) -> bool:
    try:
        os.kill(int(pid), 0)
    except (ValueError, ProcessLookupError):
        return False
    except PermissionError:  # someone else's process: alive
        pass
    return True


def make_data_dir(cell_name: str, need_bytes: int) -> DataDir:
    """This run's directory, beside `.jax_cache`; what a run that died left
    behind under the same cell's name goes first."""
    base_dir = os.path.join(harness.ROOT, DATA_ROOT)
    kind, mount = refd.filesystem(harness.ROOT)
    note = ""
    if not refd.on_disk(kind):
        tmp_kind, tmp_mount = refd.filesystem(tempfile.gettempdir())
        if refd.on_disk(tmp_kind):
            base_dir = os.path.join(tempfile.gettempdir(), "tmtpu-" + DATA_ROOT.strip("."))
            note = f" (under TMPDIR: the checkout is on {kind})"
            kind, mount = tmp_kind, tmp_mount
        else:
            note = (" (memory: neither the checkout nor TMPDIR is on a disk filesystem; "
                    "fsyncs are free here)")
    os.makedirs(base_dir, exist_ok=True)
    for name in os.listdir(base_dir):
        stem, _, pid = name.rpartition("-")
        if stem == cell_name and not _alive(pid):
            shutil.rmtree(os.path.join(base_dir, name), ignore_errors=True)
    root = os.path.join(base_dir, f"{cell_name}-{os.getpid()}")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    st = os.statvfs(root)
    free = st.f_bavail * st.f_frsize
    say(f"durable: data directory {root} on {kind} at {mount}{note}; "
        f"{free / 2**20:.0f} MiB free, the run needs about {need_bytes / 2**20:.0f}")
    if free < need_bytes:
        raise RuntimeError(f"{root}: {free} bytes free, the run needs about {need_bytes}")
    return DataDir(root=root, fs=kind + note, on_disk=refd.on_disk(kind), free_bytes=free)


def dir_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f))
               for d, _dirs, files in os.walk(path) for f in files)


def fsync_ms(path: str, n: int = 32, size: int = 48 * 1024) -> float:
    """Median ms of an fsync after a `size`-byte append in `path`: what one
    synced commit's fsync costs on this filesystem, read during set-up."""
    import statistics

    times = []
    name = os.path.join(path, "fsync.probe")
    with open(name, "ab") as f:
        for _ in range(n):
            f.write(b"\0" * size)
            f.flush()
            t0 = time.perf_counter()
            os.fsync(f.fileno())
            times.append(time.perf_counter() - t0)
    os.remove(name)
    return 1e3 * statistics.median(times)


@dataclass
class OpenedNode:
    stores: list = field(default_factory=list)
    #: the per-DB counters once the node stood at genesis, handshaken: what
    #: it took to open it is not the apply path's
    ready: dict = field(default_factory=dict)


@contextlib.contextmanager
def node_on_disk(data_dir: str):
    """While open, `fixtures.fresh_node` — what `blocksync._sync` builds its
    node with — gives the same node (kvstore app, block store, state store,
    handshake, executor) over `open_node_stores(data_dir)`. On the way out
    the stores are closed: the program's connections are gone before anyone
    else reads the files."""
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.proxy import AppConns
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.blockstore import BlockStore

    node = OpenedNode()

    async def fresh_node(genesis):
        stores = open_node_stores(data_dir)
        node.stores.append(stores)
        app = KVStoreApp(stores.app_db)
        conns = AppConns.local(app)
        bstore, sstore = BlockStore(stores.block_db), StateStore(stores.state_db)
        state = await Handshaker(
            sstore, state_from_genesis(genesis), bstore, genesis
        ).handshake(conns)
        sstore.save(state)
        ex = BlockExecutor(sstore, conns.consensus, block_store=bstore)
        node.ready = db_counters()
        return app, conns, bstore, state, ex

    orig = fixtures.fresh_node
    fixtures.fresh_node = fresh_node
    try:
        yield node
    finally:
        fixtures.fresh_node = orig
        for stores in node.stores:
            stores.close()


def db_counters() -> dict:
    """The program's per-DB counters, flat: `block.sync_commits`, ..."""
    return {f"{name}.{k}": v for name, c in COUNTERS.items() for k, v in c.items()}


# -- warm-up and window ----------------------------------------------------------------


def warmup(fx, cfg: dict, cell: dict, spans: harness.Spans) -> list[str]:
    """`blocksync.warmup` with the warm-up node on disk too, in a directory
    of its own: the window's node is fresh."""
    p = cell["traffic"]
    # a height keeps about 57 KB, and each file's WAL a few MB beside it
    need = (p["blocks"] + p["warmup_blocks"]) * 64 * 1024 + 64 * 2**20
    data = fx.observed["data"] = make_data_dir(cell["name"], need)
    fx.observed["fsync_ms"] = fsync_ms(data.root)
    say(f"durable: an fsync after a 48 KB append takes {fx.observed['fsync_ms']:.3f} ms here")
    with node_on_disk(os.path.join(data.root, "warm")):
        shapes = base.warmup(fx, cfg, cell, spans)
    return shapes + ["three SQLite files under a data directory (no program)"]


@dataclass
class Window(base.Window):
    extra: dict = field(default_factory=dict)

    @property
    def report(self) -> dict:
        return dict(super().report, **self.extra)


def window(fx, cfg: dict, cell: dict, seconds: float, patches: harness.Patches,
           trace, spans: harness.Spans, on_close=lambda: None) -> Window:
    data: DataDir = fx.observed["data"]
    node_dir = os.path.join(data.root, "node")
    opened_at = db_counters()
    marks: dict = {}

    def closing() -> None:
        on_close()
        marks["close"] = db_counters()

    with node_on_disk(node_dir) as node:
        t_open = time.perf_counter()
        w = base.window(fx, cfg, cell, seconds, patches, trace, spans, closing)
    # the measured node's whole life (read for its synced commits: its `gets`
    # hold every meta `_sync` read back once the run was over)
    life = harness.delta(db_counters(), opened_at)
    in_window = harness.delta(marks["close"], node.ready)
    durable_readers.note_window(w.t0, w.t1, in_window)
    files_closed(node_dir)
    fx.observed.update(node_dir=node_dir, life=life)
    extra = {
        "data_fs": data.fs,
        "data_bytes": dir_bytes(node_dir),
        "data_free_bytes": data.free_bytes,
        "fsync_ms": fx.observed["fsync_ms"],
        **durable_readers.report(w.t0, w.t1, w.units),
    }
    say(f"durable window: node files {extra['data_bytes']} bytes on {data.fs}; node open "
        f"{time.perf_counter() - t_open:.1f}s; counters over its life {life}")
    return Window(**{f: getattr(w, f) for f in ("sync", "elapsed", "t0", "t1", "units", "trace")},
                  extra=extra)


# -- the comparison ---------------------------------------------------------------------


def _outside(name: str, value: float, lo: float, hi: float) -> Check:
    """How far `value` lies outside [lo, hi]: 0 inside."""
    return Check(name, max(lo - value, value - hi, 0), 0)


async def _second_pass(genesis, node_dir: str) -> dict:
    """The NORMAL path over the same files: what a restarted node does."""
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.proxy import AppConns
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.blockstore import BlockStore

    stores = open_node_stores(node_dir)
    try:
        app = KVStoreApp(stores.app_db)
        conns = AppConns.local(app)
        bstore, sstore = BlockStore(stores.block_db), StateStore(stores.state_db)
        hs = Handshaker(sstore, sstore.load() or state_from_genesis(genesis), bstore, genesis)
        try:
            state = await hs.handshake(conns)
        finally:
            await conns.stop()
        return {"replayed": hs.n_blocks_replayed, "store_height": bstore.height(),
                "state_height": state.last_block_height, "state_app_hash": state.app_hash,
                "app_height": app.height, "app_hash": app.app_hash}
    finally:
        stores.close()


def compare(fx, w: Window, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    """`blocksync.compare`, then the files by the plain reference, then the
    handshake over them; the data directory goes at the end."""
    data: DataDir = fx.observed["data"]
    node_dir = fx.observed["node_dir"]
    try:
        checks, attempted, failed = base.compare(fx, w, d, spans)
        s, chain = w.sync, fx.chain
        applied = sorted(set(s.applied))
        served = {h: refd.served_block_bytes(chain.wire[h]) for h in applied}
        f = refd.read_files(node_dir, applied, served, chain.block_hash_at, chain.app_hash_at,
                            len(chain.txs_at[1]))
        last = applied[-1] if applied else 0
        behind = f["store_height"] - f["state_height"]
        w.extra["cut_inside_apply"] = bool(behind or f["app_height"] != f["state_height"])
        life = fx.observed["life"]
        say(f"durable: files say store {f['store_height']}, state {f['state_height']}, app "
            f"{f['app_height']}; the run reported {len(applied)} heights applied (last {last})")
        checks += [
            Check("block_rows_missing", f["block_rows_missing"], 0),
            Check("block_bytes_mismatches", f["block_bytes_mismatches"], 0),
            _outside("state_height_behind_store", behind, 0, 1),
            Check("applied_ahead_of_state", max(0, last - f["state_height"]), 0),
            _outside("app_height_off", f["app_height"] - f["state_height"], 0, 1),
            Check("app_hash_mismatch.files", f["app_hash_mismatch"], 0),
            Check("app_rows_off", f["app_rows_off"], 0),
            Check("block_sync_commits_short",
                  max(0.0, len(applied) - life.get("block.sync_commits", 0.0)), 0),
            Check("state_sync_commits_short",
                  max(0.0, 2 * len(applied) - life.get("state.sync_commits", 0.0)), 0),
            Check("data_fs_on_disk", int(data.on_disk), 1, "min"),
        ]
        t0 = time.perf_counter()
        top = f["store_height"]
        try:
            again = asyncio.run(_second_pass(chain.genesis, node_dir))
        except Exception as e:  # noqa: BLE001 — a handshake that gives up is a fault, not a crash
            say(f"durable: handshake over the reopened files FAILED: {e!r}")
            again, faults = {"replayed": 0}, 1
        else:
            faults = (
                int(not again["store_height"] == again["state_height"] == again["app_height"]
                    == top)
                + int(again["app_hash"] != chain.app_hash_at.get(top))
                + int(again["state_app_hash"] != chain.app_hash_at.get(top)))
            say(f"durable: handshake over the reopened files replayed {again['replayed']} "
                f"block(s) to height {again['app_height']} in {time.perf_counter() - t0:.2f}s")
        w.extra["handshake_replayed_blocks"] = again["replayed"]
        checks += [
            Check("handshake_replayed_blocks", again["replayed"], 1),
            Check("handshake_faults", faults, 0),
        ]
        return checks, attempted, failed
    finally:
        shutil.rmtree(data.root, ignore_errors=True)
