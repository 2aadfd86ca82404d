"""RouterNet-XL — multi-process committees over real sockets, with
process-level fault injection and socket-layer chaos.

Every earlier soak shares one interpreter, so committee scale is
GIL-bound and chaos only ever exercised the in-memory transport.
RouterNet-XL splits the committee across K worker OS processes:

  * each worker hosts a SLICE of RouterNodes (`XLSliceNet`, a RouterNet
    that builds only its indices);
  * intra-slice links stay on the memory transport; cross-slice links
    run over real TCP or UDS with the full SecretConnection handshake
    (`p2p/tcp.py` finally carrying consensus load);
  * one `XLNet` supervisor owns spawn/join/teardown, drives the
    scenario event script over a small protoenc control protocol, and
    aggregates per-worker reports + wedge dumps into one structured
    outcome (the chaos_soak contract: bounded, structured, never
    hangs);
  * verification amortizes host-wide: workers point their VerifyHub at
    one verifyd sidecar via `TMTPU_VERIFYD_SOCK`; killing the daemon
    mid-soak degrades every worker to inline-local (hub breaker), never
    wedges.

Chaos ports to the socket layer unchanged: RouterShell chaos-wraps the
socket transport exactly like the memory transport, so drops, corrupt
frames, delay, bandwidth shaping and partitions apply at the TCP
frame boundary. Determinism across processes comes from
`ChaosConfig.link_seeded`: every (src, dst) link draws from its own
`random.Random(f"{seed}:{src}:{dst}")` stream, so a link's fault
schedule depends only on its own message sequence — identical no
matter which process hosts which end.

Process-level faults are first-class scenario events:

  * `kill_worker` (Event.node = worker index): SIGKILL the worker
    process group — torn WAL tails on every node in the slice;
  * `restart_worker`: respawn it. Durable per-node stores (SQLite) +
    consensus-WAL open-time repair + SecretConnection re-handshake +
    reactor catch-up gossip recover the whole slice;
  * `kill_verifyd`: SIGKILL the shared verification sidecar.

Determinism contract (ROADMAP split): frozen-clock in-process runs keep
pinning bytes; wall-clock multi-process runs pin app-hash chains (pure
functions of the committed tx sequence) plus the audit invariants —
zero conflicting honest commits, evidence accountability — aggregated
across workers.

Identities are pure functions of the node index (RouterShell key_seed
"routernet"), so every process derives every node's key, id and byz
plan from (scenario, seed) alone — the control protocol moves only
endpoints, events, heights and reports, never key material.
"""

from __future__ import annotations

import asyncio
import hashlib
import json
import os
import random
import signal
import subprocess
import sys
import tempfile
from dataclasses import dataclass, replace

from ..crypto import ed25519
from ..libs import protoenc as pe
from ..libs.chaos import ChaosNetwork
from ..p2p.tcp import TCPTransport, UDSTransport
from ..p2p.types import NodeAddress, node_id_from_pubkey
from .byzantine import audit_net, byz_prepare_hook
from .harness import make_genesis  # noqa: F401  (re-export for callers)
from .routernet import RouterNet, committee_config, topology_edges
from .scenarios import (
    SCENARIOS,
    Event,
    Scenario,
    _churn_tx,
    _event_indices,
    _inject_tx,
    _resolve_group,
    _snapshot_wedge,
    _write_wedge,
)

# -- control protocol -------------------------------------------------------
#
# Supervisor <-> worker frames over the control UDS:
#   [4-byte BE length][protoenc message], field 1 = frame type.
# Bounds are enforced BEFORE allocation (the decode-bound discipline —
# a hostile/corrupt worker stream must not OOM the supervisor).

MAX_CTL_FRAME = 16 * 1024 * 1024
MAX_XL_NODES = 2048  # endpoints / heights / node-reports per frame
MAX_XL_CHAIN = 4096  # hash-chain entries per node report
MAX_XL_DIAG = 4 * 1024 * 1024  # diagnostic JSON blob per report

CTL_HELLO = 1
CTL_TOPOLOGY = 2
CTL_GO = 3
CTL_EVENT = 4
CTL_STATUS = 5
CTL_STOP = 6
CTL_REPORT = 7


@dataclass(frozen=True)
class CtlHello:
    """Worker -> supervisor: my slice's socket listen endpoints.
    Re-sent after an in-worker node restart re-binds a listener."""

    worker: int
    endpoints: tuple[tuple[int, str], ...] = ()  # (global index, endpoint)


@dataclass(frozen=True)
class CtlTopology:
    """Supervisor -> workers: the merged index -> endpoint map."""

    endpoints: tuple[tuple[int, str], ...] = ()


@dataclass(frozen=True)
class CtlGo:
    """Supervisor -> worker: start consensus. `preload` holds on
    respawn too: mempool contents died with the process, and if the
    worker was SIGKILLed before height 1 its txs exist nowhere else —
    an empty respawned mempool would let an empty height-1 block
    diverge from the in-process control. Re-injection is safe for the
    deterministic workload: already-committed txs are purged from the
    mempool as catch-up replays blocks, and the kv txs are idempotent
    assignments, so even a duplicate commit leaves the app-hash chain
    unchanged."""

    preload: bool = True


@dataclass(frozen=True)
class CtlEvent:
    """One scenario event, broadcast to every worker; group tuples ride
    as (bounded) JSON strings — they mix ints with the literal "rest"."""

    action: str
    node: int = 0
    delay_us: int = 0
    power: int = 1
    groups_json: str = ""
    src_json: str = ""
    dst_json: str = ""


@dataclass(frozen=True)
class CtlStatus:
    worker: int
    heights: tuple[tuple[int, int], ...] = ()  # (global index, height)


@dataclass(frozen=True)
class CtlStop:
    wedged: bool = False  # ask the worker for a wedge dump


@dataclass(frozen=True)
class NodeReport:
    index: int
    height: int
    app_hashes: tuple[bytes, ...] = ()  # heights 1..len
    block_hashes: tuple[bytes, ...] = ()
    evidence: int = 0  # evidence committed in this node's chain


@dataclass(frozen=True)
class CtlReport:
    worker: int
    nodes: tuple[NodeReport, ...] = ()
    diag_json: bytes = b""  # faults/audit/byz/wedge-path diagnostics
    error: str = ""


def _encode_endpoint(index: int, endpoint: str) -> bytes:
    return pe.varint_field(1, index) + pe.string_field(2, endpoint)


def _encode_node_report(nr: NodeReport) -> bytes:
    out = pe.varint_field(1, nr.index) + pe.varint_field(2, nr.height)
    # chain entries ride as embedded messages (always emitted, even for
    # an empty hash) — bytes_field's proto3 default-elision would shift
    # every later height down a slot and fabricate cross-node conflicts
    for h in nr.app_hashes:
        out += pe.message_field(3, pe.bytes_field(1, h))
    for h in nr.block_hashes:
        out += pe.message_field(4, pe.bytes_field(1, h))
    out += pe.varint_field(5, nr.evidence)
    return out


def _unwrap_hash(data: bytes) -> bytes:
    if not data:
        return b""
    r = pe.Reader(data)
    out = b""
    while not r.eof():
        f, wt = r.read_tag()
        if f == 1:
            out = r.read_bytes()
        else:
            r.skip(wt)
    return out


def encode_ctl(msg) -> bytes:
    """Encode one control frame body (the 4-byte length prefix is the
    stream framer's job — see write_ctl)."""
    if isinstance(msg, CtlHello):
        body = pe.varint_field(2, msg.worker)
        for i, ep in msg.endpoints:
            body += pe.message_field(3, _encode_endpoint(i, ep))
        return pe.varint_field(1, CTL_HELLO) + body
    if isinstance(msg, CtlTopology):
        body = b"".join(
            pe.message_field(3, _encode_endpoint(i, ep))
            for i, ep in msg.endpoints
        )
        return pe.varint_field(1, CTL_TOPOLOGY) + body
    if isinstance(msg, CtlGo):
        return pe.varint_field(1, CTL_GO) + pe.bool_field(2, msg.preload)
    if isinstance(msg, CtlEvent):
        body = pe.string_field(2, msg.action)
        body += pe.varint_field(3, msg.node & 0xFFFFFFFF)
        body += pe.varint_field(4, msg.delay_us)
        body += pe.varint_field(5, msg.power)
        if msg.groups_json:
            body += pe.string_field(6, msg.groups_json)
        if msg.src_json:
            body += pe.string_field(7, msg.src_json)
        if msg.dst_json:
            body += pe.string_field(8, msg.dst_json)
        return pe.varint_field(1, CTL_EVENT) + body
    if isinstance(msg, CtlStatus):
        body = pe.varint_field(2, msg.worker)
        for i, h in msg.heights:
            body += pe.message_field(
                3, pe.varint_field(1, i) + pe.varint_field(2, h)
            )
        return pe.varint_field(1, CTL_STATUS) + body
    if isinstance(msg, CtlStop):
        return pe.varint_field(1, CTL_STOP) + pe.bool_field(2, msg.wedged)
    if isinstance(msg, CtlReport):
        body = pe.varint_field(2, msg.worker)
        for nr in msg.nodes:
            body += pe.message_field(3, _encode_node_report(nr))
        if msg.diag_json:
            body += pe.bytes_field(4, msg.diag_json)
        if msg.error:
            body += pe.string_field(5, msg.error)
        return pe.varint_field(1, CTL_REPORT) + body
    raise TypeError(f"unknown control message {type(msg).__name__}")


def _decode_endpoint(data: bytes) -> tuple[int, str]:
    r = pe.Reader(data)
    idx, ep = 0, ""
    while not r.eof():
        f, wt = r.read_tag()
        if f == 1:
            idx = r.read_uvarint()
        elif f == 2:
            ep = r.read_string()
        else:
            r.skip(wt)
    return idx, ep


def _decode_node_report(data: bytes) -> NodeReport:
    r = pe.Reader(data)
    idx = height = evidence = 0
    app_hashes: list[bytes] = []
    block_hashes: list[bytes] = []
    while not r.eof():
        f, wt = r.read_tag()
        if f == 1:
            idx = r.read_uvarint()
        elif f == 2:
            height = r.read_uvarint()
        elif f == 3:
            app_hashes.append(_unwrap_hash(r.read_bytes()))
            pe.check_repeat(app_hashes, MAX_XL_CHAIN, "xl app hashes")
        elif f == 4:
            block_hashes.append(_unwrap_hash(r.read_bytes()))
            pe.check_repeat(block_hashes, MAX_XL_CHAIN, "xl block hashes")
        elif f == 5:
            evidence = r.read_uvarint()
        else:
            r.skip(wt)
    return NodeReport(
        idx, height, tuple(app_hashes), tuple(block_hashes), evidence
    )


def decode_ctl(data: bytes):
    """Decode one control frame body; every repeated field is bounded
    and the diagnostic blob capped (MAX_XL_DIAG) before it is kept."""
    r = pe.Reader(data)
    ftype = None
    worker = node = delay_us = 0
    power = 1
    preload = wedged = False
    action = groups_json = src_json = dst_json = error = ""
    endpoints: list[tuple[int, str]] = []
    heights: list[tuple[int, int]] = []
    nodes: list[NodeReport] = []
    diag = b""
    while not r.eof():
        f, wt = r.read_tag()
        if f == 1:
            ftype = r.read_uvarint()
        elif f == 2:
            if ftype == CTL_EVENT:
                action = r.read_string()
            elif ftype in (CTL_GO, CTL_STOP):
                flag = bool(r.read_uvarint())
                preload = wedged = flag
            else:
                worker = r.read_uvarint()
        elif f == 3:
            if ftype in (CTL_HELLO, CTL_TOPOLOGY):
                endpoints.append(_decode_endpoint(r.read_bytes()))
                pe.check_repeat(endpoints, MAX_XL_NODES, "xl endpoints")
            elif ftype == CTL_STATUS:
                er = pe.Reader(r.read_bytes())
                i = h = 0
                while not er.eof():
                    ef, ewt = er.read_tag()
                    if ef == 1:
                        i = er.read_uvarint()
                    elif ef == 2:
                        h = er.read_uvarint()
                    else:
                        er.skip(ewt)
                heights.append((i, h))
                pe.check_repeat(heights, MAX_XL_NODES, "xl heights")
            elif ftype == CTL_REPORT:
                nodes.append(_decode_node_report(r.read_bytes()))
                pe.check_repeat(nodes, MAX_XL_NODES, "xl node reports")
            else:
                node = r.read_uvarint()
        elif f == 4:
            if ftype == CTL_EVENT:
                delay_us = r.read_uvarint()
            else:
                diag = r.read_bytes()
                if len(diag) > MAX_XL_DIAG:
                    raise ValueError("xl diag blob exceeds bound")
        elif f == 5:
            if ftype == CTL_EVENT:
                power = r.read_uvarint()
            else:
                error = r.read_string()
        elif f == 6:
            groups_json = r.read_string()
        elif f == 7:
            src_json = r.read_string()
        elif f == 8:
            dst_json = r.read_string()
        else:
            r.skip(wt)
    if ftype == CTL_HELLO:
        return CtlHello(worker, tuple(endpoints))
    if ftype == CTL_TOPOLOGY:
        return CtlTopology(tuple(endpoints))
    if ftype == CTL_GO:
        return CtlGo(preload)
    if ftype == CTL_EVENT:
        # Event.node references are taken mod n, so the unsigned wrap in
        # encode round-trips negative indices (node=-1 = last node)
        if node >= 0x80000000:
            node -= 0x100000000
        return CtlEvent(
            action, node, delay_us, power, groups_json, src_json, dst_json
        )
    if ftype == CTL_STATUS:
        return CtlStatus(worker, tuple(heights))
    if ftype == CTL_STOP:
        return CtlStop(wedged)
    if ftype == CTL_REPORT:
        return CtlReport(worker, tuple(nodes), diag, error)
    raise ValueError(f"unknown control frame type {ftype}")


async def write_ctl(writer: asyncio.StreamWriter, msg) -> None:
    data = encode_ctl(msg)
    if len(data) > MAX_CTL_FRAME:
        raise ValueError("control frame exceeds bound")
    writer.write(len(data).to_bytes(4, "big") + data)
    await writer.drain()


async def read_ctl(reader: asyncio.StreamReader):
    hdr = await reader.readexactly(4)
    n = int.from_bytes(hdr, "big")
    if n > MAX_CTL_FRAME:
        raise ValueError("oversized control frame")
    return decode_ctl(await reader.readexactly(n))


def event_to_ctl(ev: Event) -> CtlEvent:
    return CtlEvent(
        action=ev.action,
        node=ev.node,
        delay_us=int(ev.delay_ms * 1000),
        power=ev.power,
        groups_json=json.dumps(ev.groups) if ev.groups else "",
        src_json=json.dumps(ev.src) if ev.src else "",
        dst_json=json.dumps(ev.dst) if ev.dst else "",
    )


def ctl_to_event(c: CtlEvent) -> Event:
    def _grp(s: str) -> tuple:
        return tuple(json.loads(s)) if s else ()

    def _grps(s: str) -> tuple:
        return tuple(tuple(g) for g in json.loads(s)) if s else ()

    return Event(
        at_s=0.0,
        action=c.action,
        groups=_grps(c.groups_json),
        src=_grp(c.src_json),
        dst=_grp(c.dst_json),
        node=c.node,
        delay_ms=c.delay_us / 1000.0,
        power=c.power,
    )


# -- identities -------------------------------------------------------------

_NODE_ID_CACHE: dict[int, str] = {}


def xl_node_id(index: int) -> str:
    """Node id of RouterNet node `index` — RouterShell's derivation
    (key_seed "routernet"), computable in ANY process without building
    the node. The cross-process partition/gray events resolve indices
    through this."""
    nid = _NODE_ID_CACHE.get(index)
    if nid is None:
        priv = ed25519.Ed25519PrivKey(
            hashlib.sha256(f"tmtpu:routernet:{index}".encode()).digest()
        )
        nid = node_id_from_pubkey(priv.pub_key())
        _NODE_ID_CACHE[index] = nid
    return nid


def slice_assignment(n_vals: int, workers: int) -> list[list[int]]:
    """Contiguous balanced slices, worker w hosting slice w — a pure
    function of (n_vals, workers) so every process computes it."""
    base, extra = divmod(n_vals, workers)
    out, start = [], 0
    for w in range(workers):
        size = base + (1 if w < extra else 0)
        out.append(list(range(start, start + size)))
        start += size
    return out


def xl_topology_edges(
    n: int,
    degree: int,
    seed: int,
    slices: list[list[int]],
    bridges: int = 4,
) -> list[tuple[int, int]]:
    """Locality-aware topology for multi-process nets: each slice keeps
    the standard seeded RouterNet topology internally (those links ride
    the memory transport — cheap), while each PAIR of slices gets at
    most `bridges` deterministic bridge edges — the only links that pay
    the real-socket + SecretConnection AEAD cost. Gossip relay carries
    votes/parts through the bridges, so connectivity (slice subgraphs
    are connected, slice pairs are bridged) is all consensus needs.
    Without this, a 500-validator × 4-worker net wires ~1500 encrypted
    cross-process links and — on images where the AEAD is pure Python —
    vote gossip can't reach quorum within any wall budget; with it, the
    encrypted link count is K·(K−1)/2 · bridges. Pure function of
    (n, degree, seed, slices, bridges): every worker derives the same
    edge set without coordination."""
    edges: set[tuple[int, int]] = set()
    for sl in slices:
        for a, b in topology_edges(len(sl), degree, seed):
            ga, gb = sl[a], sl[b]
            edges.add((min(ga, gb), max(ga, gb)))
    rng = random.Random(
        f"routernet-xl-topo:{seed}:{n}:{len(slices)}:{bridges}"
    )
    for ai in range(len(slices)):
        for bi in range(ai + 1, len(slices)):
            sa, sb = slices[ai], slices[bi]
            want = min(bridges, len(sa) * len(sb))
            picked: set[tuple[int, int]] = set()
            attempts = 0
            while len(picked) < want and attempts < 50 * max(1, want):
                attempts += 1
                a = sa[rng.randrange(len(sa))]
                b = sb[rng.randrange(len(sb))]
                if a != b:
                    picked.add((min(a, b), max(a, b)))
            edges |= picked
    return sorted(edges)


def preload_txs(seed: int, count: int) -> list[bytes]:
    """The deterministic workload every validator preloads before Go:
    the committed tx sequence — and therefore the app-hash chain — is a
    pure function of (seed, count), which is what lets a wall-clock
    multi-process run be compared hash-for-hash against a frozen-clock
    in-process control run."""
    return [f"xl:{seed}:{k}=v{k}".encode() for k in range(count)]


# -- the worker-side slice net ---------------------------------------------


class XLSliceNet(RouterNet):
    """A RouterNet that builds only `slice_indices` of the committee.
    Each local node carries its memory transport (intra-slice links)
    plus one TCP/UDS transport (cross-slice links), both chaos-wrapped
    by RouterShell. Cross-slice wiring happens in `wire_topology` once
    the supervisor broadcasts the merged endpoint map."""

    def __init__(
        self,
        n_vals: int,
        *,
        slice_indices,
        transport_kind: str = "tcp",
        state_dir: str | None = None,
        durable: bool = True,
        workers: int | None = None,
        locality: bool = True,
        bridges: int = 4,
        **kw,
    ):
        self.slice_indices = tuple(sorted(slice_indices))
        self.transport_kind = transport_kind
        self.state_dir = state_dir or tempfile.mkdtemp(prefix="xl-slice-")
        # unix-transport socket paths live here even when stores are
        # not durable — the directory must exist either way
        os.makedirs(self.state_dir, exist_ok=True)
        self.durable = durable
        self.sock_transports: dict[int, TCPTransport] = {}
        super().__init__(n_vals, **kw)
        if locality and workers and workers > 1:
            # bound the encrypted cross-process link count: dense
            # in-slice (memory transport), `bridges` links per slice
            # pair (real sockets). Every worker derives the same set.
            self.edges = xl_topology_edges(
                self.n,
                kw.get("degree", 8),
                kw.get("topo_seed", 0),
                slice_assignment(self.n, workers),
                bridges,
            )
        self.by_index = {node.index: node for node in self.nodes}

    def _build_nodes(self):
        return [self._build_node(i) for i in self.slice_indices]

    def _extra_transports_for(self, index: int) -> list:
        if self.transport_kind == "memory":
            return []
        cls = TCPTransport if self.transport_kind == "tcp" else UDSTransport
        t = cls()
        self.sock_transports[index] = t
        return [t]

    def _build_node(self, i, *, app=None, block_store=None,
                    state_store=None, wal_dir=None):
        if self.durable:
            # durable per-node stores: a SIGKILLed worker's respawn
            # recovers block/state/app from SQLite + consensus-WAL
            # open-time repair — the CLI node's persistence shape.
            # (MemDB stores would leave the WAL AHEAD of state, which
            # catchup_replay correctly refuses as a double-sign hazard.)
            from ..abci.kvstore import KVStoreApp
            from ..state.store import StateStore
            from ..store.blockstore import BlockStore
            from ..store.db import SQLiteDB

            d = os.path.join(self.state_dir, f"n{i}")
            os.makedirs(d, exist_ok=True)
            if app is None and self._app_factory is None:
                app = KVStoreApp(SQLiteDB(os.path.join(d, "app.db")))
            if block_store is None:
                block_store = BlockStore(SQLiteDB(os.path.join(d, "blocks.db")))
            if state_store is None:
                state_store = StateStore(SQLiteDB(os.path.join(d, "state.db")))
            wal_dir = wal_dir or os.path.join(d, "wal")
        return super()._build_node(
            i, app=app, block_store=block_store, state_store=state_store,
            wal_dir=wal_dir,
        )

    def _connect(self) -> None:
        # wiring waits for the supervisor's topology broadcast
        pass

    async def listen(self) -> dict[int, str]:
        """Bind every local node's socket transport; returns the
        index -> endpoint map for the Hello frame."""
        eps: dict[int, str] = {}
        for i, t in sorted(self.sock_transports.items()):
            if self.transport_kind == "tcp":
                await t.listen("127.0.0.1:0")
                eps[i] = t.endpoint()
            else:
                path = os.path.join(self.state_dir, f"n{i}.sock")
                try:
                    os.unlink(path)
                except FileNotFoundError:
                    pass
                await t.listen(path)
                eps[i] = path
        return eps

    async def listen_one(self, index: int) -> str:
        """Re-bind one node's transport after an in-worker restart."""
        t = self.sock_transports[index]
        if self.transport_kind == "tcp":
            await t.listen("127.0.0.1:0")
            return t.endpoint()
        path = os.path.join(self.state_dir, f"n{index}.sock")
        try:
            os.unlink(path)
        except FileNotFoundError:
            pass
        await t.listen(path)
        return path

    def _sock_address(self, index: int, endpoint: str) -> NodeAddress:
        if self.transport_kind == "tcp":
            host, _, port = endpoint.rpartition(":")
            return NodeAddress(
                node_id=xl_node_id(index), protocol="tcp",
                host=host, port=int(port),
            )
        return NodeAddress(
            node_id=xl_node_id(index), protocol="unix",
            host=endpoint, port=0,
        )

    def wire_topology(self, endpoints: dict[int, str]) -> None:
        """Add peer addresses for every topology edge touching this
        slice: memory for local-local, socket for cross-slice (both
        sides dial; the router dedups the double connection). Safe to
        re-run on every topology broadcast — a respawned worker's new
        endpoints just become additional dial candidates."""
        local = self.by_index
        for a, b in self.edges:
            if a in local and b in local:
                local[a].shell.peer_manager.add_address(
                    local[b].shell.address()
                )
            elif a in local or b in local:
                li, ri = (a, b) if a in local else (b, a)
                ep = endpoints.get(ri)
                if ep:
                    local[li].shell.peer_manager.add_address(
                        self._sock_address(ri, ep)
                    )

    # crash/restart by GLOBAL index (RouterNet's are positional)

    def _pos(self, gi: int) -> int:
        for p, node in enumerate(self.nodes):
            if node.index == gi:
                return p
        raise KeyError(gi)

    async def crash(self, gi: int) -> None:
        node = self.by_index[gi]
        fs = node.fs
        if fs is not None:
            fs.halt()
        await node.stop()
        if fs is not None:
            fs.simulate_crash()

    async def restart(self, gi: int):
        old = self.by_index[gi]
        node = self._build_node(
            gi,
            app=old.inner.app,
            block_store=old.inner.block_store,
            state_store=old.inner.state_store,
            wal_dir=old.inner.wal_dir,
        )
        self.nodes[self._pos(gi)] = node
        self.by_index[gi] = node
        await node.start()
        return node


# -- worker process ---------------------------------------------------------


def _load_cfg(ctl_sock: str) -> dict:
    with open(
        os.path.join(os.path.dirname(ctl_sock), "xl_config.json"),
        encoding="utf-8",
    ) as f:
        return json.load(f)


def _resolve_scenario(cfg: dict) -> Scenario:
    scenario = SCENARIOS[cfg["scenario"]]
    if cfg.get("chaos_overrides"):
        scenario = replace(
            scenario, chaos=replace(scenario.chaos, **cfg["chaos_overrides"])
        )
    return scenario


def _build_slice(cfg: dict, widx: int, run_dir: str) -> XLSliceNet:
    scenario = _resolve_scenario(cfg)
    seed = cfg["seed"]
    n_vals = cfg["n_vals"]
    slices = slice_assignment(n_vals, cfg["workers"])
    chaos_cfg = replace(scenario.chaos, seed=seed, link_seeded=True)
    chaos = (
        ChaosNetwork(chaos_cfg)
        if (chaos_cfg.enabled() or scenario.events)
        else None
    )
    fs_factory = None
    if scenario.fs is not None:
        from ..libs.chaosfs import ChaosFS

        fs_cfg = scenario.fs

        def fs_factory(i: int, _cfg=fs_cfg, _seed=seed):
            return ChaosFS(replace(_cfg, seed=_seed * 1009 + i))

    config = None
    if (
        n_vals > 16
        or scenario.storm_timeouts
        or scenario.byz
        or scenario.byz_f_max is not None
    ):
        # storm-sized timers whenever rounds may churn: at committee
        # scale, under declared vote storms, and — multi-process
        # specific — whenever traitors withhold/lie over real sockets,
        # where per-frame AEAD + handshake latency makes fast
        # sub-second timers churn rounds faster than honest relay
        # gossip can heal the starved peers (steps advance on quorum,
        # not timers, so generous timers cost the happy path nothing).
        config = committee_config(max(n_vals, 10))
    byz_plan = {}
    for idx, bcfg in scenario.byz:
        i = idx % n_vals
        byz_plan[i] = replace(bcfg, seed=seed * 1013 + i)
    if scenario.byz_f_max is not None:
        f = max(0, (n_vals - 1) // 3)
        for i in range(n_vals - f, n_vals):
            byz_plan.setdefault(
                i, replace(scenario.byz_f_max, seed=seed * 1013 + i)
            )
    byz_registry: list = []
    net = XLSliceNet(
        n_vals,
        slice_indices=slices[widx],
        transport_kind=cfg.get("transport", "tcp"),
        state_dir=os.path.join(run_dir, f"w{widx}"),
        durable=cfg.get("durable", True),
        workers=cfg["workers"],
        locality=cfg.get("locality", True),
        bridges=cfg.get("bridges", 4),
        config=config,
        chaos=chaos,
        base_clock=None,  # wall-clock: multi-process runs pin app hashes
        degree=cfg.get("degree", 8),
        topo_seed=seed,
        gossip_sleep=cfg.get("gossip_sleep"),
        use_hub=True,
        fs_factory=fs_factory,
        prepare_hook=(
            byz_prepare_hook(byz_plan, byz_registry) if byz_plan else None
        ),
    )
    net._byz_plan = byz_plan
    net._byz_registry = byz_registry
    net._scenario = scenario
    return net


async def _apply_xl_event(ev: Event, net: XLSliceNet, seed: int) -> None:
    """Worker-side event application: identical semantics to
    scenarios._apply_event, with index -> node-id resolution through
    `xl_node_id` (events name GLOBAL indices; this slice may host none
    of them) and crash/restart applied only to local nodes."""
    n = net.n
    chaos = net.chaos
    named = _event_indices(ev, n)
    ids = lambda idxs: {xl_node_id(i) for i in idxs}  # noqa: E731
    if ev.action.startswith("churn_"):
        tx, expect_reject = _churn_tx(ev, net, seed)
        await _inject_tx(net, tx, expect_reject=expect_reject)
    elif ev.action == "partition":
        chaos.partition(*(ids(_resolve_group(g, n, named)) for g in ev.groups))
    elif ev.action == "oneway":
        chaos.partition_oneway(
            ids(_resolve_group(ev.src, n, named)),
            ids(_resolve_group(ev.dst, n, named)),
        )
    elif ev.action == "heal":
        chaos.heal()
    elif ev.action == "gray":
        chaos.set_gray(xl_node_id(ev.node % n), ev.delay_ms)
    elif ev.action == "ungray":
        chaos.set_peer_config(xl_node_id(ev.node % n), chaos.config)
    elif ev.action in ("crash", "restart"):
        gi = ev.node % n
        if gi in net.by_index:
            if ev.action == "crash":
                await net.crash(gi)
            else:
                await net.restart(gi)
                return gi  # caller re-binds the listener + re-Hellos
    else:
        raise ValueError(f"unknown xl event action {ev.action!r}")
    return None


def _slice_report(net: XLSliceNet, widx: int, diag: dict, error: str) -> CtlReport:
    nodes = []
    for node in net.nodes:
        store = node.inner.block_store
        height = store.height()
        upto = min(height, MAX_XL_CHAIN)
        app_hashes, block_hashes, evidence = [], [], 0
        for h in range(1, upto + 1):
            blk = store.load_block(h)
            if blk is None:
                app_hashes.append(b"")
                block_hashes.append(b"")
                continue
            app_hashes.append(blk.header.app_hash)
            block_hashes.append(blk.hash())
            evidence += len(blk.evidence)
        nodes.append(
            NodeReport(
                node.index, height, tuple(app_hashes), tuple(block_hashes),
                evidence,
            )
        )
    blob = json.dumps(diag, default=str).encode()
    if len(blob) > MAX_XL_DIAG:
        blob = json.dumps({"truncated": True}).encode()
    return CtlReport(widx, tuple(nodes), blob, error)


async def _worker(ctl_sock: str, widx: int, respawn: bool) -> int:
    cfg = await asyncio.to_thread(_load_cfg, ctl_sock)
    run_dir = os.path.dirname(ctl_sock)
    seed = cfg["seed"]
    net = _build_slice(cfg, widx, run_dir)
    scenario = net._scenario
    reader, writer = await asyncio.open_unix_connection(ctl_sock)
    error = ""
    stop_wedged = False
    event_tasks: set[asyncio.Task] = set()
    from ..crypto import verify_hub as vh

    hub = vh.acquire_hub()
    try:
        for node in net.nodes:
            await node.prepare()
        eps = await net.listen()
        await write_ctl(writer, CtlHello(widx, tuple(sorted(eps.items()))))

        started = False
        status_task: asyncio.Task | None = None

        async def status_loop():
            while True:
                await asyncio.sleep(cfg.get("status_interval_s", 0.4))
                hs = tuple(
                    (node.index, node.inner.block_store.height())
                    for node in net.nodes
                )
                try:
                    await write_ctl(writer, CtlStatus(widx, hs))
                except (ConnectionError, OSError):
                    return

        async def handle_event(ev: Event):
            rebind = await _apply_xl_event(ev, net, seed)
            if rebind is not None:
                ep = await net.listen_one(rebind)
                eps[rebind] = ep
                net.wire_topology(dict(_topology[0]))
                await write_ctl(
                    writer, CtlHello(widx, tuple(sorted(eps.items())))
                )

        _topology: list[dict[int, str]] = [{}]
        while True:
            msg = await read_ctl(reader)
            if isinstance(msg, CtlTopology):
                _topology[0] = dict(msg.endpoints)
                net.wire_topology(_topology[0])
            elif isinstance(msg, CtlGo):
                if not started:
                    if msg.preload:
                        txs = preload_txs(seed, cfg.get("preload_txs", 8))
                        from ..mempool.pool import (
                            TxInCacheError,
                            TxRejectedError,
                        )

                        for node in net.nodes:
                            for tx in txs:
                                try:
                                    await node.inner.mempool.check_tx(tx)
                                except (TxInCacheError, TxRejectedError):
                                    pass
                    await asyncio.gather(*(node.go() for node in net.nodes))
                    status_task = asyncio.get_running_loop().create_task(
                        status_loop()
                    )
                    started = True
            elif isinstance(msg, CtlEvent):
                t = asyncio.get_running_loop().create_task(
                    handle_event(ctl_to_event(msg))
                )
                event_tasks.add(t)
                t.add_done_callback(event_tasks.discard)
            elif isinstance(msg, CtlStop):
                stop_wedged = msg.wedged
                break
        if status_task is not None:
            status_task.cancel()
            await asyncio.gather(status_task, return_exceptions=True)
    except (asyncio.IncompleteReadError, ConnectionError, OSError) as e:
        error = f"control link lost: {e!r}"
    except Exception as e:  # noqa: BLE001 — reported, never a silent exit
        error = repr(e)
    finally:
        for t in event_tasks:
            t.cancel()
        await asyncio.gather(*event_tasks, return_exceptions=True)
        # build + send the report best-effort, then tear down
        try:
            audit = audit_net(
                net,
                net._byz_registry,
                k_heights=cfg.get("audit_k", 3),
                require_evidence=(
                    scenario.audit_require_evidence
                    and bool(net._byz_registry)
                ),
            ).as_dict()
        except Exception as e:  # noqa: BLE001
            audit = {"ok": False, "notes": [f"audit failed: {e!r}"]}
        diag = {
            "worker": widx,
            "slice": list(net.slice_indices),
            "faults": dict(net.chaos.faults) if net.chaos else {},
            "audit": audit,
            "byz": [b.log_summary() for b in net._byz_registry],
        }
        try:
            diag["verify_stats"] = hub.stats()
        except Exception:  # noqa: BLE001 — diagnostics only
            diag["verify_stats"] = {}
        if stop_wedged or error:
            payload = _snapshot_wedge(
                scenario, net, net.chaos,
                {"worker": widx, "seed": seed, "error": error},
            )
            try:
                diag["wedge_dump"] = await asyncio.to_thread(
                    _write_wedge,
                    os.path.join(run_dir, "dumps"),
                    f"w{widx}",
                    payload,
                )
            except Exception as e:  # noqa: BLE001
                diag["wedge_dump_error"] = repr(e)
        try:
            await write_ctl(writer, _slice_report(net, widx, diag, error))
        except (ConnectionError, OSError):
            pass
        try:
            writer.close()
        except Exception:
            pass
        await net.stop()
        vh.release_hub()
    return 1 if error else 0


def worker_main(argv: list[str] | None = None) -> int:
    """Worker process entry: `python -c "...; worker_main()" <ctl_sock>
    <worker_index> <fresh|respawn>` (spawned by XLNet)."""
    argv = argv if argv is not None else sys.argv[1:]
    ctl_sock, widx, mode = argv[0], int(argv[1]), argv[2]
    return asyncio.run(_worker(ctl_sock, widx, respawn=(mode == "respawn")))


# -- supervisor -------------------------------------------------------------


def aggregate_reports(
    reports: dict[int, CtlReport],
    *,
    byz_indices: set[int],
    require_evidence: bool,
) -> dict:
    """Cross-worker safety aggregation: every node that committed a
    height must agree on its block hash AND app hash (zero conflicting
    commits, net-wide) and every worker's local audit must pass.

    Accountability is enforced by the per-worker `audit_net` runs, not
    re-derived here: committed evidence rides the shared chain, so the
    worker hosting a twin-producing traitor fails its own audit if the
    evidence never lands — while withhold/flood strategies that never
    double-sign legitimately commit zero evidence. `evidence_total`
    (duplicate-vote evidence observed on honest chains) is surfaced as
    telemetry; `require_evidence` only annotates the notes when traitors
    were installed and no evidence committed anywhere."""
    block_conflicts: list[int] = []
    app_conflicts: list[int] = []
    by_h_block: dict[int, set[bytes]] = {}
    by_h_app: dict[int, set[bytes]] = {}
    evidence_total = 0
    worker_audits_ok = True
    notes: list[str] = []
    for rep in reports.values():
        try:
            diag = json.loads(rep.diag_json or b"{}")
        except ValueError:
            diag = {}
        audit = diag.get("audit") or {}
        if not audit.get("ok", False):
            worker_audits_ok = False
            notes.append(f"worker {rep.worker} audit: {audit.get('notes')}")
        for nr in rep.nodes:
            if nr.index not in byz_indices:
                evidence_total += nr.evidence
            for h0, bh in enumerate(nr.block_hashes):
                if bh:
                    by_h_block.setdefault(h0 + 1, set()).add(bh)
            for h0, ah in enumerate(nr.app_hashes):
                if ah:
                    by_h_app.setdefault(h0 + 1, set()).add(ah)
    block_conflicts = sorted(h for h, s in by_h_block.items() if len(s) > 1)
    app_conflicts = sorted(h for h, s in by_h_app.items() if len(s) > 1)
    if byz_indices and require_evidence and evidence_total == 0:
        # informational: worker audits decide whether this is a failure
        # (only twin-producing equivocators owe committed evidence)
        notes.append("no committed evidence on honest chains")
    return {
        "ok": (
            not block_conflicts
            and not app_conflicts
            and worker_audits_ok
        ),
        "block_conflicts": block_conflicts,
        "app_conflicts": app_conflicts,
        "worker_audits_ok": worker_audits_ok,
        "evidence_total": evidence_total,
        "notes": notes,
    }


class XLNet:
    """The supervisor: owns worker spawn/join/teardown, the control
    UDS, the optional verifyd sidecar, the scenario event script
    (socket-chaos events broadcast to workers; process faults applied
    here), the aggregated liveness watchdog, and report collection.
    `run()` returns one structured outcome dict — the chaos_soak
    contract (bounded wall clock, never raises on a wedge)."""

    def __init__(
        self,
        scenario: Scenario | str = "baseline",
        *,
        n_vals: int = 4,
        workers: int = 2,
        transport: str = "tcp",
        seed: int = 1,
        target_height: int = 4,
        timeout_s: float = 180.0,
        stall_s: float = 60.0,
        time_scale: float = 1.0,
        process_events: tuple[Event, ...] = (),
        use_verifyd: bool = False,
        preload: int = 8,
        durable: bool = True,
        gossip_sleep: float | None = None,
        degree: int = 8,
        locality: bool = True,
        bridges: int = 4,
        chaos_overrides: dict | None = None,
        status_interval_s: float = 0.4,
        report_timeout_s: float = 60.0,
        run_dir: str | None = None,
    ):
        if isinstance(scenario, str):
            scenario = SCENARIOS[scenario]
        self.scenario = scenario
        self.n_vals = n_vals
        self.workers = workers
        self.transport = transport
        self.seed = seed
        self.target_height = target_height
        self.timeout_s = timeout_s
        self.stall_s = stall_s
        self.time_scale = time_scale
        self.process_events = tuple(process_events)
        self.use_verifyd = use_verifyd
        self.preload = preload
        self.durable = durable
        self.gossip_sleep = gossip_sleep
        self.degree = degree
        self.locality = locality
        self.bridges = bridges
        self.chaos_overrides = chaos_overrides
        self.status_interval_s = status_interval_s
        self.report_timeout_s = report_timeout_s
        self.run_dir = run_dir
        self.slices = slice_assignment(n_vals, workers)
        # byz plan mirrors the worker derivation (supervisor needs the
        # indices for the honest-min watchdog + evidence aggregation)
        self.byz_indices: set[int] = {i % n_vals for i, _ in scenario.byz}
        if scenario.byz_f_max is not None:
            f = max(0, (n_vals - 1) // 3)
            self.byz_indices |= set(range(n_vals - f, n_vals))
        # runtime state
        self.procs: dict[int, subprocess.Popen] = {}
        self.conns: dict[int, asyncio.StreamWriter] = {}
        self.endpoints: dict[int, str] = {}
        self.status: dict[int, int] = {}
        self.reports: dict[int, CtlReport] = {}
        self.dead_workers: set[int] = set()
        self.hello_events: dict[int, asyncio.Event] = {}
        self.verifyd_proc: subprocess.Popen | None = None
        self.verifyd_sock: str | None = None
        self._server: asyncio.AbstractServer | None = None
        self._ctl_sock: str | None = None

    # -- process management (spawn/join ride to_thread: the supervisor
    # loop also carries the control server and the watchdog) ------------

    def _worker_env(self) -> dict:
        import tendermint_tpu

        repo_root = os.path.dirname(
            os.path.dirname(os.path.abspath(tendermint_tpu.__file__))
        )
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            TMTPU_DISABLE_TPU="1",
            PYTHONPATH=repo_root,
        )
        env.setdefault("TMTPU_MAX_BUCKET", "64")
        if self.verifyd_sock:
            env["TMTPU_VERIFYD_SOCK"] = self.verifyd_sock
        else:
            env.pop("TMTPU_VERIFYD_SOCK", None)
        return env

    async def _spawn_worker(self, widx: int, mode: str) -> None:
        log_path = os.path.join(self.run_dir, f"worker{widx}.log")
        self.hello_events.setdefault(widx, asyncio.Event()).clear()

        def _spawn():
            with open(log_path, "ab") as logf:
                return subprocess.Popen(
                    [
                        sys.executable,
                        "-c",
                        "import sys; "
                        "from tendermint_tpu.consensus.routernet_xl "
                        "import worker_main; sys.exit(worker_main())",
                        self._ctl_sock,
                        str(widx),
                        mode,
                    ],
                    env=self._worker_env(),
                    stdout=logf,
                    stderr=logf,
                    start_new_session=True,
                )

        self.procs[widx] = await asyncio.to_thread(_spawn)

    async def _kill_worker(self, widx: int) -> None:
        proc = self.procs.get(widx)
        if proc is None:
            return
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        await asyncio.to_thread(proc.wait)
        self.dead_workers.add(widx)
        # frozen stale heights must not satisfy the watchdog
        for gi in self.slices[widx]:
            self.status.pop(gi, None)
        w = self.conns.pop(widx, None)
        if w is not None:
            try:
                w.close()
            except Exception:
                pass

    async def _spawn_verifyd(self) -> None:
        """The shared sidecar. Who owns the chip here: NOBODY. Workers
        are CPU-pinned with the probe off (_worker_env); the daemon
        inherits JAX_PLATFORMS=cpu and only drops TMTPU_DISABLE_TPU, so
        its probe attaches the JAX-CPU backend (active kind "cpu") — the
        rig exercises the socket protocol and cross-process packing, not
        a device, and no child wants a chip the supervisor's process
        might hold."""
        self.verifyd_sock = os.path.join(self.run_dir, "verifyd.sock")
        env = self._worker_env()
        env.pop("TMTPU_DISABLE_TPU", None)
        env.pop("TMTPU_VERIFYD_SOCK", None)
        log_path = os.path.join(self.run_dir, "verifyd.log")

        def _spawn():
            with open(log_path, "ab") as logf:
                return subprocess.Popen(
                    [
                        sys.executable,
                        "-c",
                        "import sys; from tendermint_tpu.cli import main; "
                        f"sys.exit(main(['verifyd', '--sock', "
                        f"{self.verifyd_sock!r}, '--no-warm']))",
                    ],
                    env=env,
                    stdout=logf,
                    stderr=logf,
                    start_new_session=True,
                )

        self.verifyd_proc = await asyncio.to_thread(_spawn)
        # wait for the daemon socket to come up
        deadline = asyncio.get_running_loop().time() + 60.0
        while asyncio.get_running_loop().time() < deadline:
            stats = await asyncio.to_thread(self._verifyd_stats)
            if stats is not None:
                return
            await asyncio.sleep(0.25)
        raise TimeoutError("verifyd never came up")

    def _verifyd_stats(self) -> dict | None:
        from ..crypto.verifyd import client_for

        if not self.verifyd_sock:
            return None
        try:
            return client_for(self.verifyd_sock).remote_stats()  # tmtlint: allow[verify-chokepoint] -- occupancy telemetry probe, not a verify path
        except Exception:  # noqa: BLE001 — absent/killed daemon is a state
            return None

    async def _kill_verifyd(self) -> None:
        if self.verifyd_proc is None:
            return
        try:
            os.killpg(self.verifyd_proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        await asyncio.to_thread(self.verifyd_proc.wait)

    # -- control server --------------------------------------------------

    async def _handle_conn(
        self, reader: asyncio.StreamReader, writer: asyncio.StreamWriter
    ) -> None:
        widx: int | None = None
        try:
            while True:
                msg = await read_ctl(reader)
                if isinstance(msg, CtlHello):
                    widx = msg.worker
                    self.conns[widx] = writer
                    self.endpoints.update(dict(msg.endpoints))
                    self.dead_workers.discard(widx)
                    self.hello_events.setdefault(widx, asyncio.Event()).set()
                    # every (re-)hello changes the endpoint map: rebroadcast
                    await self._broadcast(
                        CtlTopology(tuple(sorted(self.endpoints.items())))
                    )
                elif isinstance(msg, CtlStatus):
                    for gi, h in msg.heights:
                        self.status[gi] = h
                elif isinstance(msg, CtlReport):
                    self.reports[msg.worker] = msg
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            pass
        finally:
            # a dead worker's EOF leaves the transport half-open until
            # the writer is closed, and from Python 3.12
            # Server.wait_closed() waits for every such transport
            writer.close()

    async def _broadcast(self, msg, *, only: int | None = None) -> None:
        targets = (
            [only]
            if only is not None
            else [w for w in self.conns if w not in self.dead_workers]
        )
        for w in targets:
            writer = self.conns.get(w)
            if writer is None:
                continue
            try:
                await write_ctl(writer, msg)
            except (ConnectionError, OSError):
                pass

    # -- observation -----------------------------------------------------

    def honest_min(self) -> int:
        dead_nodes = {
            gi for w in self.dead_workers for gi in self.slices[w]
        }
        alive = [
            gi
            for gi in range(self.n_vals)
            if gi not in self.byz_indices and gi not in dead_nodes
        ]
        if not alive:
            alive = [gi for gi in range(self.n_vals) if gi not in dead_nodes]
        if not alive:
            return 0
        return min(self.status.get(gi, 0) for gi in alive)

    def honest_max(self) -> int:
        """Highest committed height on any live honest node — the
        stall watchdog's progress signal: a commit ANYWHERE means 2/3
        precommits existed, so the committee is converging, not wedged
        (at 500 validators on one core, catch-up spread of a committed
        height to the LAST node takes minutes — honest_min alone would
        misread that window as a stall)."""
        dead_nodes = {
            gi for w in self.dead_workers for gi in self.slices[w]
        }
        heights = [
            h
            for gi, h in self.status.items()
            if gi not in self.byz_indices and gi not in dead_nodes
        ]
        return max(heights, default=0)

    # -- the run ---------------------------------------------------------

    async def run(self) -> dict:
        loop = asyncio.get_running_loop()
        if self.run_dir is None:
            self.run_dir = await asyncio.to_thread(
                tempfile.mkdtemp, prefix="xl-run-"
            )
        self._ctl_sock = os.path.join(self.run_dir, "ctl.sock")
        cfg = {
            "scenario": self.scenario.name,
            "seed": self.seed,
            "n_vals": self.n_vals,
            "workers": self.workers,
            "transport": self.transport,
            "durable": self.durable,
            "degree": self.degree,
            "locality": self.locality,
            "bridges": self.bridges,
            "gossip_sleep": self.gossip_sleep,
            "preload_txs": self.preload,
            "status_interval_s": self.status_interval_s,
            "chaos_overrides": self.chaos_overrides,
        }

        def _write_cfg():
            with open(
                os.path.join(self.run_dir, "xl_config.json"),
                "w",
                encoding="utf-8",
            ) as f:
                json.dump(cfg, f)

        await asyncio.to_thread(_write_cfg)
        out: dict = {
            "outcome": "error",
            "scenario": self.scenario.name,
            "seed": self.seed,
            "n_vals": self.n_vals,
            "workers": self.workers,
            "transport": self.transport,
            "target_height": self.target_height,
            "events_applied": [],
            "process_events_applied": [],
            "heights": {},
            "honest_min": 0,
            "elapsed_s": 0.0,
            "blocks_per_s": 0.0,
            "recover_s": None,
            "faults": {},
            "audit": None,
            "app_hash_chain": [],
            "verifyd": None,
            "worker_errors": [],
            "dump_paths": [],
            "run_dir": self.run_dir,
            "error": "",
        }
        ok = wedged = False
        error = ""
        t0 = t_done = loop.time()
        events_task: asyncio.Task | None = None
        last_event_t = [t0]
        try:
            self._server = await asyncio.start_unix_server(
                self._handle_conn, self._ctl_sock
            )
            if self.use_verifyd:
                await self._spawn_verifyd()
            for w in range(self.workers):
                self.hello_events[w] = asyncio.Event()
            for w in range(self.workers):
                await self._spawn_worker(w, "fresh")
            await asyncio.wait_for(
                asyncio.gather(
                    *(self.hello_events[w].wait() for w in range(self.workers))
                ),
                self.timeout_s,
            )
            await self._broadcast(
                CtlTopology(tuple(sorted(self.endpoints.items())))
            )
            await self._broadcast(CtlGo(True))
            t0 = loop.time()

            events = sorted(
                (*self.scenario.events, *self.process_events),
                key=lambda e: e.at_s,
            )

            async def drive_events():
                for ev in events:
                    await asyncio.sleep(
                        max(0.0, ev.at_s * self.time_scale - (loop.time() - t0))
                    )
                    try:
                        if ev.action == "kill_worker":
                            await self._kill_worker(ev.node % self.workers)
                            out["process_events_applied"].append(
                                f"kill_worker:{ev.node % self.workers}"
                            )
                        elif ev.action == "restart_worker":
                            w = ev.node % self.workers
                            await self._spawn_worker(w, "respawn")
                            await asyncio.wait_for(
                                self.hello_events[w].wait(), 120.0
                            )
                            await self._broadcast(
                                CtlGo(self.preload > 0), only=w
                            )
                            out["process_events_applied"].append(
                                f"restart_worker:{w}"
                            )
                        elif ev.action == "kill_verifyd":
                            await self._kill_verifyd()
                            out["process_events_applied"].append("kill_verifyd")
                        else:
                            await self._broadcast(event_to_ctl(ev))
                            out["events_applied"].append(ev.action)
                    except Exception as e:  # noqa: BLE001 — recorded
                        out["worker_errors"].append(
                            f"event {ev.action}@{ev.at_s}: {e!r}"
                        )
                    last_event_t[0] = loop.time()

            events_task = loop.create_task(drive_events(), name="xl.events")

            # -- aggregated liveness watchdog (run_scenario's gate) ----
            deadline = t0 + self.timeout_s
            last_min = -1
            last_progress = loop.time()
            post_event_target: int | None = (
                self.target_height if not events else None
            )
            while True:
                await asyncio.sleep(0.25)
                mh = self.honest_min()
                now = loop.time()
                # stall resets on progress ANYWHERE (honest_max): a
                # commit on any node proves quorum; the min-height
                # target below still gates success on full catch-up
                if max(mh, self.honest_max()) > last_min:
                    last_min = max(mh, self.honest_max())
                    last_progress = now
                if post_event_target is None and events_task.done():
                    post_event_target = max(self.target_height, mh + 1)
                if post_event_target is not None and mh >= post_event_target:
                    ok = True
                    t_done = now
                    break
                if (
                    now > deadline
                    or (now - last_progress) > self.stall_s * self.time_scale
                ):
                    wedged = True
                    t_done = now
                    break
        except Exception as e:  # noqa: BLE001 — structured outcome contract
            error = repr(e)
            t_done = loop.time()
        finally:
            if events_task is not None:
                events_task.cancel()
                await asyncio.gather(events_task, return_exceptions=True)
            # verifyd occupancy BEFORE teardown (daemon may be gone: None)
            if self.use_verifyd:
                out["verifyd"] = await asyncio.to_thread(self._verifyd_stats)
            # collect reports from every live worker
            await self._broadcast(CtlStop(wedged or bool(error)))
            waited = loop.time()
            want = set(range(self.workers)) - self.dead_workers
            while (
                want - set(self.reports)
                and loop.time() - waited < self.report_timeout_s
            ):
                await asyncio.sleep(0.2)
            # teardown: SIGKILL anything still running, reap off-loop
            for w, proc in self.procs.items():
                if proc.poll() is None:
                    try:
                        os.killpg(proc.pid, signal.SIGKILL)
                    except ProcessLookupError:
                        pass
            await asyncio.gather(
                *(
                    asyncio.to_thread(p.wait)
                    for p in self.procs.values()
                ),
                return_exceptions=True,
            )
            await self._kill_verifyd()
            if self._server is not None:
                self._server.close()
                for w in list(self.conns.values()):
                    w.close()
                await self._server.wait_closed()

        agg = aggregate_reports(
            self.reports,
            byz_indices=self.byz_indices,
            require_evidence=self.scenario.audit_require_evidence,
        )
        out["audit"] = agg
        for rep in self.reports.values():
            if rep.error:
                out["worker_errors"].append(f"worker {rep.worker}: {rep.error}")
            try:
                diag = json.loads(rep.diag_json or b"{}")
            except ValueError:
                diag = {}
            if diag.get("wedge_dump"):
                out["dump_paths"].append(diag["wedge_dump"])
            for k, v in (diag.get("faults") or {}).items():
                out["faults"][k] = out["faults"].get(k, 0) + v
            for nr in rep.nodes:
                out["heights"][nr.index] = nr.height
        # canonical app-hash chain: the longest honest reported chain
        best: tuple[bytes, ...] = ()
        for rep in self.reports.values():
            for nr in rep.nodes:
                if nr.index not in self.byz_indices and len(
                    nr.app_hashes
                ) > len(best):
                    best = nr.app_hashes
        out["app_hash_chain"] = [h.hex() for h in best]
        out["honest_min"] = self.honest_min()
        elapsed = max(t_done - t0, 1e-9)
        out["elapsed_s"] = round(elapsed, 3)
        committed = out["honest_min"]
        out["blocks_per_s"] = round(committed / elapsed, 4) if ok else 0.0
        if ok and (self.scenario.events or self.process_events):
            out["recover_s"] = round(max(0.0, t_done - last_event_t[0]), 3)
        out["error"] = error
        if error:
            out["outcome"] = "error"
        elif wedged:
            out["outcome"] = "wedged"
        elif ok and agg["ok"]:
            out["outcome"] = "ok"
        else:
            out["outcome"] = "audit_failed"
        return out


async def run_xl(scenario: Scenario | str = "baseline", **kwargs) -> dict:
    """One multi-process XL run; see XLNet. Returns the structured
    outcome dict (never raises on a wedge)."""
    return await XLNet(scenario, **kwargs).run()
