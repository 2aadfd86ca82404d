"""Command-line interface (reference cmd/tendermint/commands).

  init       — write config.toml, genesis.json, node + validator keys
  start      — run a full node (builtin kvstore app) until interrupted
  testnet    — generate N validator homes with a shared genesis
  show-node-id / show-validator
  gen-node-key / gen-validator
  reset      — wipe data, keep keys/config (unsafe-reset-all)
  light      — verify a height against a running node over RPC
  inspect    — read-only report over a stopped node's data dirs
  verifyd    — run the verification sidecar (one warm device mesh
               shared by every node process on the host over a UDS)
  version
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import shutil
import sys

from . import version as _version_mod
from .config import Config, config_from_toml, config_to_toml
from .crypto import ed25519
from .p2p.types import NodeAddress, node_id_from_pubkey
from .privval import FilePV
from .types.genesis import GenesisDoc, GenesisValidator


def _home(args) -> str:
    return os.path.expanduser(args.home)


def _paths(home: str) -> dict:
    return {
        "config": os.path.join(home, "config"),
        "data": os.path.join(home, "data"),
        "config_toml": os.path.join(home, "config", "config.toml"),
        "genesis": os.path.join(home, "config", "genesis.json"),
        "node_key": os.path.join(home, "config", "node_key.json"),
        "pv_key": os.path.join(home, "config", "priv_validator_key.json"),
        "pv_state": os.path.join(home, "data", "priv_validator_state.json"),
    }


def _load_or_gen_node_key(path: str) -> ed25519.Ed25519PrivKey:
    if os.path.exists(path):
        with open(path) as f:
            return ed25519.Ed25519PrivKey(bytes.fromhex(json.load(f)["priv_key"])[:32])
    key = ed25519.Ed25519PrivKey.generate()
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(
            {
                "id": node_id_from_pubkey(key.pub_key()),
                "priv_key": key.bytes().hex(),
            },
            f,
            indent=2,
        )
    return key


def cmd_init(args) -> int:
    """Reference commands/init.go."""
    home = _home(args)
    p = _paths(home)
    os.makedirs(p["config"], exist_ok=True)
    os.makedirs(p["data"], exist_ok=True)
    if not os.path.exists(p["config_toml"]):
        cfg = Config(moniker=args.moniker or "node")
        with open(p["config_toml"], "w") as f:
            f.write(config_to_toml(cfg))
    node_key = _load_or_gen_node_key(p["node_key"])
    pv = (
        FilePV.load_or_generate(p["pv_key"], p["pv_state"])
        if args.mode == "validator"
        else None
    )
    if not os.path.exists(p["genesis"]):
        import time

        doc = GenesisDoc(
            chain_id=args.chain_id or f"test-chain-{os.urandom(3).hex()}",
            genesis_time_ns=time.time_ns(),
            validators=[GenesisValidator(pv.get_pub_key(), 10, "validator")]
            if args.mode == "validator"
            else [],
        )
        with open(p["genesis"], "w") as f:
            f.write(doc.to_json())
    print(f"initialized {args.mode} node in {home}")
    print(f"node id: {node_id_from_pubkey(node_key.pub_key())}")
    return 0


def _build_node(home: str):
    from .abci.kvstore import KVStoreApp
    from .node import Node, NodeConfig
    from .p2p.tcp import TCPTransport
    from .statesync.reactor import SyncConfig
    from .store.db import SQLiteDB, open_node_stores

    p = _paths(home)
    with open(p["config_toml"]) as f:
        cfg = config_from_toml(f.read())
    with open(p["genesis"]) as f:
        genesis = GenesisDoc.from_json(f.read())
    node_key = _load_or_gen_node_key(p["node_key"])
    # only homes initialized with a validator key sign (init full → none)
    pv = (
        FilePV.load(p["pv_key"], p["pv_state"])
        if os.path.exists(p["pv_key"])
        else None
    )
    stores = open_node_stores(p["data"], app=cfg.proxy_app == "kvstore")
    if cfg.proxy_app == "kvstore":
        app = KVStoreApp(stores.app_db)
    elif cfg.proxy_app.startswith(("tcp://", "grpc://")):
        # out-of-process app (reference config proxy_app semantics:
        # tcp://host:port = socket ABCI, grpc://host:port = gRPC ABCI)
        from .proxy import AppConns

        scheme, addr = cfg.proxy_app.split("://", 1)
        try:
            host, port_s = addr.rsplit(":", 1)
            int(port_s)
        except ValueError:
            raise SystemExit(
                f"invalid proxy_app address {cfg.proxy_app!r} "
                "(expected tcp://host:port or grpc://host:port)"
            ) from None
        if scheme == "tcp":
            from .abci.socket import SocketClient

            def factory(name: str):
                return SocketClient(host, int(port_s))
        else:
            from .abci.grpcnet import GrpcClient

            def factory(name: str):
                return GrpcClient(host, int(port_s))

        app = AppConns.from_factory(factory)
    else:
        raise SystemExit(
            f"unknown proxy app {cfg.proxy_app!r} "
            "(builtin: kvstore; remote: tcp://host:port, grpc://host:port)"
        )
    if cfg.trace.enabled and not cfg.trace.dump_dir:
        # real nodes get their flight auto-dumps next to the watchdog's
        # stack bundles unless the operator pointed them elsewhere
        cfg.trace.dump_dir = os.path.join(p["data"], "debug")
    state_sync = None
    if cfg.statesync.enable and cfg.statesync.trust_hash:
        state_sync = SyncConfig(
            trust_height=cfg.statesync.trust_height,
            trust_hash=bytes.fromhex(cfg.statesync.trust_hash),
            trust_period_ns=cfg.statesync.trust_period_ns,
        )
    node_config = NodeConfig(
        consensus=cfg.consensus,
        mempool=cfg.mempool,
        block_sync=cfg.blocksync.enable,
        state_sync=state_sync,
        moniker=cfg.moniker,
        wal_dir=os.path.join(p["data"], "cs.wal"),
        rpc_laddr=cfg.rpc.laddr if cfg.rpc.enable else "",
        rpc_pprof=cfg.rpc.pprof,
        seed_mode=cfg.mode == "seed",
        addr_book_path=os.path.join(p["config"], "addrbook.json"),
        watchdog_dir=os.path.join(p["data"], "debug") if cfg.rpc.watchdog else "",
        watchdog_threshold_s=cfg.rpc.watchdog_threshold_s,
        chaos=cfg.chaos,
        chaos_fs=cfg.chaos_fs,
        verify_hub=cfg.verify_hub,
        trace=cfg.trace,
    )
    transport = TCPTransport(
        send_rate=cfg.p2p.send_rate, recv_rate=cfg.p2p.recv_rate
    )
    node = Node(
        node_config,
        genesis,
        app,
        node_key,
        [transport],
        priv_validator=pv,
        block_db=stores.block_db,
        state_db=stores.state_db,
        evidence_db=SQLiteDB(os.path.join(p["data"], "evidence.db")),
        index_db=SQLiteDB(os.path.join(p["data"], "tx_index.db")),
    )
    return node, cfg, transport


async def _run_node(home: str) -> None:
    # _build_node is pure construction (config/genesis file reads,
    # sqlite opens) — blocking I/O, so it runs off-loop; nothing here
    # needs the loop until transport.listen below
    node, cfg, transport = await asyncio.to_thread(_build_node, home)
    await transport.listen(cfg.p2p.laddr)
    await node.start()
    for peer in filter(None, cfg.p2p.persistent_peers.split(",")):
        node.peer_manager.add_address(NodeAddress.parse(peer.strip()), persistent=True)
    # seeds: dial once for an address push (the seed disconnects after
    # serving; discovered addresses land in the address book via PEX)
    for seed in filter(None, cfg.p2p.seeds.split(",")):
        node.peer_manager.add_address(NodeAddress.parse(seed.strip()))
    print(f"node {node.node_id} running; p2p on {transport.endpoint()}", flush=True)
    stop = asyncio.Event()
    import signal

    loop = asyncio.get_running_loop()
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            loop.add_signal_handler(sig, stop.set)
        except NotImplementedError:
            pass
    await stop.wait()
    print("shutting down…", flush=True)
    await node.stop()


def cmd_start(args) -> int:
    from .libs.debug import install_debug_handlers

    home = _home(args)
    install_debug_handlers(home)  # pidfile + SIGUSR1 stack dumps
    try:
        asyncio.run(_run_node(home))
    finally:
        # a stale pidfile would let `debug kill` signal a recycled PID;
        # remove only OUR pidfile (never another live node's)
        pid_path = os.path.join(home, "node.pid")
        try:
            with open(pid_path) as f:
                if f.read().strip() == str(os.getpid()):
                    os.remove(pid_path)
        except OSError:
            pass
    return 0


def cmd_replay(args) -> int:
    """Replay the stored chain through a FRESH app instance and check the
    app-hash chain (reference commands/replay.go console replay; here the
    handshake machinery does the replay and the stores are the truth)."""

    async def run() -> int:
        from .abci.kvstore import KVStoreApp
        from .consensus.replay import Handshaker
        from .proxy import AppConns
        from .state.state import state_from_genesis
        from .state.store import StateStore
        from .store.blockstore import BlockStore
        from .store.db import MemDB, open_node_stores
        from .types.genesis import GenesisDoc

        p = _paths(_home(args))
        # tmtlint: allow[blocking-in-async] -- one-shot CLI startup read; nothing else is on the loop yet
        with open(p["genesis"]) as f:
            genesis = GenesisDoc.from_json(f.read())
        stores = open_node_stores(p["data"], app=False)
        block_store = BlockStore(stores.block_db)
        stored = StateStore(stores.state_db).load()
        # re-execute from GENESIS state (height 0) against a fresh
        # in-memory app AND a scratch state store: the replay rebuilds the
        # whole state chain from the block store without ever writing to
        # the node's real state.db
        state = state_from_genesis(genesis)
        scratch = StateStore(MemDB())
        conns = AppConns.local(KVStoreApp(MemDB()))
        await conns.start()
        try:
            from .abci.types import RequestInfo

            hs = Handshaker(scratch, state, block_store, genesis)
            final = await hs.handshake(conns)
            mismatch = stored is not None and final.app_hash != stored.app_hash
            if mismatch:
                print(
                    f"WARNING: replayed app hash {final.app_hash.hex()} != "
                    f"stored {stored.app_hash.hex()}",
                    file=sys.stderr,
                )
            info = await conns.query.info(RequestInfo())
            print(
                json.dumps(
                    {
                        "replayed_to": final.last_block_height,
                        "app_height": info.last_block_height,
                        "app_hash": info.last_block_app_hash.hex(),
                        "state_app_hash": final.app_hash.hex(),
                        "mismatch": mismatch,
                    }
                )
            )
            # scripted integrity checks must see divergence as failure
            return 1 if mismatch else 0
        finally:
            await conns.stop()

    return asyncio.run(run())


def cmd_debug(args) -> int:
    """Collect diagnostics from a live node (reference
    cmd/tendermint/commands/debug/{dump,kill}.go)."""
    from .libs.debug import collect_node_state, write_dump_bundle

    async def run() -> int:
        from .rpc.client import HTTPClient

        client = HTTPClient(args.address)
        home = _home(args)
        try:
            if args.what == "dump":
                os.makedirs(args.output_dir, exist_ok=True)
                for i in range(args.count):
                    snap = await collect_node_state(client)
                    bundle = write_dump_bundle(args.output_dir, snap, home)
                    print(f"wrote {bundle}")
                    if i + 1 < args.count:
                        await asyncio.sleep(args.interval)
                return 0
            # kill: snapshot, request a stack dump (SIGUSR1), then
            # terminate via the pidfile
            import signal as _sig

            os.makedirs(args.output_dir, exist_ok=True)
            snap = await collect_node_state(client)
            write_dump_bundle(args.output_dir, snap, home)
            # tmtlint: allow[blocking-in-async] -- debug-dump CLI: tiny pidfile read, no serving loop to starve
            with open(os.path.join(home, "node.pid")) as f:
                pid = int(f.read().strip())
            os.kill(pid, _sig.SIGUSR1)  # goroutine-dump analog
            await asyncio.sleep(1.0)
            # fresh post-signal snapshot — the state being debugged
            snap = await collect_node_state(client)
            bundle = write_dump_bundle(args.output_dir, snap, home)
            os.kill(pid, _sig.SIGTERM)
            print(f"node {pid} terminated; diagnostics in {bundle}")
            return 0
        finally:
            await client.close()

    return asyncio.run(run())


def cmd_testnet(args) -> int:
    """Generate N validator homes (reference commands/testnet.go)."""
    import time

    base = os.path.expanduser(args.output)
    n = args.validators
    key_types = [
        k.strip() for k in getattr(args, "key_types", "ed25519").split(",") if k
    ]
    pvs, node_keys = [], []
    for i in range(n):
        home = os.path.join(base, f"node{i}")
        p = _paths(home)
        os.makedirs(p["config"], exist_ok=True)
        os.makedirs(p["data"], exist_ok=True)
        if not os.path.exists(p["pv_key"]):
            pvs.append(
                FilePV.generate(
                    p["pv_key"], p["pv_state"],
                    key_type=key_types[i % len(key_types)],
                )
            )
        else:
            pvs.append(FilePV.load(p["pv_key"], p["pv_state"]))
        node_keys.append(_load_or_gen_node_key(p["node_key"]))
    doc = GenesisDoc(
        chain_id=args.chain_id or f"testnet-{os.urandom(3).hex()}",
        genesis_time_ns=time.time_ns(),
        validators=[
            GenesisValidator(pv.get_pub_key(), 10, f"val{i}")
            for i, pv in enumerate(pvs)
        ],
    )
    peers = ",".join(
        f"tcp://{node_id_from_pubkey(nk.pub_key())}@127.0.0.1:{args.base_port + 2 * i}"
        for i, nk in enumerate(node_keys)
    )
    for i in range(n):
        home = os.path.join(base, f"node{i}")
        p = _paths(home)
        cfg = Config(moniker=f"node{i}")
        cfg.p2p.laddr = f"127.0.0.1:{args.base_port + 2 * i}"
        cfg.rpc.laddr = f"127.0.0.1:{args.base_port + 2 * i + 1}"
        cfg.p2p.persistent_peers = peers
        with open(p["config_toml"], "w") as f:
            f.write(config_to_toml(cfg))
        with open(p["genesis"], "w") as f:
            f.write(doc.to_json())
    print(f"generated {n}-validator testnet in {base}")
    return 0


def cmd_show_node_id(args) -> int:
    key = _load_or_gen_node_key(_paths(_home(args))["node_key"])
    print(node_id_from_pubkey(key.pub_key()))
    return 0


def cmd_show_validator(args) -> int:
    p = _paths(_home(args))
    pv = FilePV.load(p["pv_key"], p["pv_state"])
    pub = pv.get_pub_key()
    print(json.dumps({"type": pub.TYPE, "value": pub.bytes().hex()}))
    return 0


def cmd_gen_node_key(args) -> int:
    key = ed25519.Ed25519PrivKey.generate()
    print(
        json.dumps(
            {"id": node_id_from_pubkey(key.pub_key()), "priv_key": key.bytes().hex()}
        )
    )
    return 0


def cmd_gen_validator(args) -> int:
    key = ed25519.Ed25519PrivKey.generate()
    print(
        json.dumps(
            {
                "address": key.pub_key().address().hex(),
                "pub_key": key.pub_key().bytes().hex(),
                "priv_key": key.bytes().hex(),
            }
        )
    )
    return 0


def cmd_reset(args) -> int:
    """Wipe chain data, keep config + keys; reset sign-state (reference
    unsafe-reset-all)."""
    home = _home(args)
    p = _paths(home)
    for name in ("blockstore.db", "state.db", "evidence.db", "app.db", "tx_index.db", "cs.wal"):
        path = os.path.join(p["data"], name)
        if os.path.isdir(path):
            shutil.rmtree(path)
        elif os.path.exists(path):
            os.remove(path)
    if os.path.exists(p["pv_state"]):
        with open(p["pv_state"], "w") as f:
            json.dump(
                {"height": 0, "round": 0, "step": 0, "sign_bytes": "", "signature": ""},
                f,
            )
    print(f"reset data in {home}")
    return 0


def cmd_light(args) -> int:
    """Light client: verify a height over RPC, or (with --laddr) run the
    light RPC PROXY — a JSON-RPC server whose every answer is verified
    against the trust anchor before it is returned (reference
    light/proxy/proxy.go:18)."""

    async def run() -> int:
        from .light.client import LightClient, TrustOptions
        from .rpc.client import HTTPClient, HTTPProvider

        client = HTTPClient(args.address)
        try:
            chain_id = (await client.status())["node_info"]["network"]
            provider = HTTPProvider(chain_id, client)
            anchor = await provider.light_block(args.trust_height)
            trust_hash = (
                bytes.fromhex(args.trust_hash)
                if args.trust_hash
                else anchor.header.hash()
            )
            lc = LightClient(
                chain_id,
                TrustOptions(args.trust_period * 10**9, args.trust_height, trust_hash),
                provider,
            )
            if getattr(args, "laddr", ""):
                from .light.proxy import LightProxyEnv
                from .rpc.server import RPCServer

                server = RPCServer(LightProxyEnv(lc, client))
                host, _, port = args.laddr.rpartition(":")
                await server.start(host or "127.0.0.1", int(port or 0))
                print(
                    f"light proxy for {chain_id} via {args.address} "
                    f"listening on {host or '127.0.0.1'}:{server.port}"
                )
                try:
                    await asyncio.Event().wait()  # serve until interrupted
                finally:
                    await server.stop()
                return 0
            lb = await lc.verify_light_block_at_height(args.height)
            print(
                json.dumps(
                    {
                        "height": lb.height,
                        "hash": lb.header.hash().hex().upper(),
                        "app_hash": lb.header.app_hash.hex().upper(),
                    }
                )
            )
            return 0
        finally:
            await client.close()

    return asyncio.run(run())


def cmd_inspect(args) -> int:
    """Read-only report over a stopped node's stores (reference
    internal/inspect)."""
    from .state.store import StateStore
    from .store.blockstore import BlockStore
    from .store.db import SQLiteDB

    p = _paths(_home(args))
    bs = BlockStore(SQLiteDB(os.path.join(p["data"], "blockstore.db")))
    ss = StateStore(SQLiteDB(os.path.join(p["data"], "state.db")))
    state = ss.load()
    report = {
        "block_store": {"base": bs.base(), "height": bs.height()},
        "state": {
            "chain_id": state.chain_id if state else None,
            "last_block_height": state.last_block_height if state else 0,
            "app_hash": state.app_hash.hex() if state else "",
            "validators": len(state.validators) if state and state.validators else 0,
        },
    }
    print(json.dumps(report, indent=2))
    return 0


def cmd_version(args) -> int:
    print(_version_mod.VERSION)
    return 0


def cmd_verifyd(args) -> int:
    """Run the verification sidecar (crypto/verifyd.py): one process
    owns the warm device mesh + compile cache and serves batched
    signature verification to every node process on this host over a
    Unix-domain socket. Point nodes at it with TMTPU_VERIFYD_SOCK or
    `[verify_hub] verifyd_sock`. With --stats, query a RUNNING daemon's
    telemetry instead (attach counts, occupancy, shed) and print JSON."""
    import logging

    from .crypto.verifyd import VerifyDaemon, client_for

    sock = os.path.expanduser(args.sock) or os.path.join(_home(args), "verifyd.sock")
    if args.stats:
        stats = client_for(sock).remote_stats()  # tmtlint: allow[verify-chokepoint] -- operator telemetry query, not a verify path
        if stats is None:
            print(f"no verifyd reachable on {sock}", file=sys.stderr)
            return 1
        print(json.dumps(stats, indent=2, sort_keys=True))
        return 0

    logging.basicConfig(level=logging.INFO, format="%(name)s %(message)s")

    async def run() -> None:
        daemon = VerifyDaemon(
            sock,
            max_batch=args.max_batch,
            window_ms=args.window_ms,
            cache_size=args.cache,
            max_inflight=args.max_inflight,
            warm_backend=not args.no_warm,
        )
        await daemon.start()
        print(f"verifyd listening on {sock}", flush=True)
        stop = asyncio.Event()
        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:
                pass
        try:
            await stop.wait()
        finally:
            await daemon.stop()

    asyncio.run(run())
    return 0


def cmd_signer_harness(args) -> int:
    """Acceptance-test a remote signer (reference
    tools/tm-signer-harness/main.go:1)."""
    import logging

    from .tools import signer_harness as sh

    logging.basicConfig(level=logging.INFO, format="%(name)s %(message)s")
    expected = None
    if args.genesis:
        from .types.genesis import GenesisDoc

        with open(args.genesis) as f:
            doc = GenesisDoc.from_json(f.read())
        if not doc.validators:
            print("genesis has no validators", file=sys.stderr)
            return sh.ERR_INVALID_PARAMS
        expected = doc.validators[0].pub_key
    return sh.run_harness(
        args.addr, chain_id=args.chain_id, expected_pub_key=expected
    )


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="tendermint-tpu", description="TPU-native BFT consensus node"
    )
    parser.add_argument("--home", default="~/.tendermint_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    p_init = sub.add_parser("init", help="initialize a node home")
    p_init.add_argument("mode", nargs="?", default="validator", choices=["validator", "full"])
    p_init.add_argument("--chain-id", default="")
    p_init.add_argument("--moniker", default="")
    p_init.set_defaults(fn=cmd_init)

    p_start = sub.add_parser("start", help="run the node")
    p_start.set_defaults(fn=cmd_start)

    p_testnet = sub.add_parser("testnet", help="generate a local testnet")
    p_testnet.add_argument("--validators", "-v", type=int, default=4)
    p_testnet.add_argument("--output", "-o", default="./testnet")
    p_testnet.add_argument("--chain-id", default="")
    p_testnet.add_argument("--base-port", type=int, default=26656)
    p_testnet.add_argument(
        "--key-types",
        default="ed25519",
        help="comma-separated validator key types, cycled (ed25519,secp256k1)",
    )
    p_testnet.set_defaults(fn=cmd_testnet)

    sub.add_parser("show-node-id").set_defaults(fn=cmd_show_node_id)
    sub.add_parser("show-validator").set_defaults(fn=cmd_show_validator)
    sub.add_parser("gen-node-key").set_defaults(fn=cmd_gen_node_key)
    sub.add_parser("gen-validator").set_defaults(fn=cmd_gen_validator)
    sub.add_parser("reset", help="wipe chain data (unsafe-reset-all)").set_defaults(
        fn=cmd_reset
    )
    sub.add_parser("inspect", help="report over a stopped node").set_defaults(
        fn=cmd_inspect
    )
    sub.add_parser("version").set_defaults(fn=cmd_version)

    p_vd = sub.add_parser(
        "verifyd",
        help="run the verification sidecar (one warm device mesh shared "
        "by every node process on this host over a Unix socket)",
    )
    p_vd.add_argument(
        "--sock", default="", help="UDS path (default <home>/verifyd.sock)"
    )
    p_vd.add_argument("--max-batch", type=int, default=None)
    p_vd.add_argument("--window-ms", type=float, default=None)
    p_vd.add_argument("--cache", type=int, default=None)
    p_vd.add_argument(
        "--max-inflight",
        type=int,
        default=None,
        help="in-flight signature cap before busy-shedding",
    )
    p_vd.add_argument(
        "--no-warm",
        action="store_true",
        help="skip the startup backend probe/warm (tests)",
    )
    p_vd.add_argument(
        "--stats",
        action="store_true",
        help="query a running daemon's telemetry as JSON and exit",
    )
    p_vd.set_defaults(fn=cmd_verifyd)

    p_sh = sub.add_parser(
        "signer-harness",
        help="acceptance-test a remote signer (tm-signer-harness analog)",
    )
    p_sh.add_argument("--addr", required=True, help="tcp://h:p or grpc://h:p")
    p_sh.add_argument("--chain-id", default="harness-chain")
    p_sh.add_argument("--genesis", default="", help="pin identity to genesis validator[0]")
    p_sh.set_defaults(fn=cmd_signer_harness)

    p_light = sub.add_parser("light", help="light-verify a height over RPC")
    p_light.add_argument("--address", default="http://127.0.0.1:26657")
    p_light.add_argument("--height", type=int, default=0)
    p_light.add_argument("--trust-height", type=int, default=1)
    p_light.add_argument("--trust-hash", default="")
    p_light.add_argument("--trust-period", type=int, default=7 * 24 * 3600)
    p_light.add_argument(
        "--laddr",
        default="",
        help="run the verifying RPC proxy on this host:port instead of a one-shot verify",
    )
    p_light.set_defaults(fn=cmd_light)

    p_replay = sub.add_parser(
        "replay", help="re-execute the stored chain through a fresh app"
    )
    p_replay.set_defaults(fn=cmd_replay)

    p_debug = sub.add_parser("debug", help="collect diagnostics from a live node")
    p_debug.add_argument("what", choices=["dump", "kill"])
    p_debug.add_argument("--address", default="http://127.0.0.1:26657")
    p_debug.add_argument("--output-dir", default="./debug-dump")
    p_debug.add_argument("--count", type=int, default=1)
    p_debug.add_argument("--interval", type=float, default=5.0)
    p_debug.set_defaults(fn=cmd_debug)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
