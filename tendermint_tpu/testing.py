"""Test fixtures: deterministic validator sets, signed commits, and
chains — the analog of the reference's internal test factories. Used by the
unit tests, chip_smoke.py and benchmark/fixtures.py; not part of the public API surface."""

from __future__ import annotations

import hashlib

from .crypto import ed25519
from .crypto.hashes import sha256
from .types.block import BlockID, Commit, CommitSig, PartSetHeader
from .types.keys import SignedMsgType
from .types.validator_set import Validator, ValidatorSet
from .types.vote import Vote
from .types.canonical import vote_sign_bytes


def det_priv_keys(n: int, seed: bytes = b"tmtpu-test") -> list[ed25519.Ed25519PrivKey]:
    return [
        ed25519.Ed25519PrivKey(hashlib.sha256(seed + i.to_bytes(4, "big")).digest())
        for i in range(n)
    ]


def make_validator_set(
    n: int,
    power: int = 10,
    seed: bytes = b"tmtpu-test",
    key_types: tuple[str, ...] = ("ed25519",),
) -> tuple[ValidatorSet, dict[bytes, object]]:
    """Deterministic validator set; `key_types` cycles over the validators
    (e.g. ("ed25519", "secp256k1") alternates key types — the BASELINE
    config-4 mixed-set shape)."""
    keys: list = []
    for i in range(n):
        kt = key_types[i % len(key_types)]
        secret = hashlib.sha256(seed + kt.encode() + i.to_bytes(4, "big")).digest()
        if kt == "ed25519":
            keys.append(ed25519.Ed25519PrivKey(secret))
        elif kt == "secp256k1":
            from .crypto.secp256k1 import Secp256k1PrivKey

            keys.append(Secp256k1PrivKey(secret))
        elif kt == "sr25519":
            from .crypto.sr25519 import Sr25519PrivKey

            keys.append(Sr25519PrivKey(secret))
        elif kt == "bls12381":
            from .crypto.bls import BLSPrivKey

            keys.append(BLSPrivKey(secret))
        else:
            raise ValueError(f"unknown key type {kt}")
    vals = ValidatorSet([Validator(k.pub_key(), power) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    return vals, by_addr


def make_block_id(tag: bytes = b"blk") -> BlockID:
    return BlockID(sha256(tag), PartSetHeader(1, sha256(b"parts" + tag)))


def make_commit(
    chain_id: str,
    height: int,
    round_: int,
    block_id: BlockID,
    vals: ValidatorSet,
    keys_by_addr: dict,
    *,
    nil_indices: frozenset[int] = frozenset(),
    absent_indices: frozenset[int] = frozenset(),
    timestamp_ns: int = 1_700_000_000_000_000_000,
) -> Commit:
    """Build a fully-signed commit over `block_id` by the validator set."""
    from .types.block import NIL_BLOCK_ID

    sigs = []
    for i, val in enumerate(vals.validators):
        if i in absent_indices:
            sigs.append(CommitSig.absent())
            continue
        ts = timestamp_ns + i
        vote_bid = NIL_BLOCK_ID if i in nil_indices else block_id
        sb = vote_sign_bytes(
            chain_id, SignedMsgType.PRECOMMIT, height, round_, vote_bid, ts
        )
        sig = keys_by_addr[val.address].sign(sb)
        if i in nil_indices:
            sigs.append(CommitSig.for_nil(val.address, ts, sig))
        else:
            sigs.append(CommitSig.for_block(val.address, ts, sig))
    return Commit(height, round_, block_id, tuple(sigs))


def make_light_chain(
    n_heights: int,
    vals: ValidatorSet,
    keys_by_addr: dict,
    chain_id: str = "light-chain",
    *,
    start_time_ns: int = 1_700_000_000_000_000_000,
    block_interval_ns: int = 1_000_000_000,
):
    """A synthetic chain of properly-signed LightBlocks 1..n_heights
    over one static validator set: hash-linked headers with monotone
    times, each committed by the full set — the light-client serving /
    hop-proof workload shape (LightFleet tests) without spinning a live
    network."""
    from .crypto.hashes import sha256 as _sha
    from .light.types import LightBlock, SignedHeader
    from .types.block import Header

    out: list = []
    last_bid = BlockID()
    vh = vals.hash()
    for h in range(1, n_heights + 1):
        header = Header(
            chain_id=chain_id,
            height=h,
            time_ns=start_time_ns + h * block_interval_ns,
            last_block_id=last_bid,
            last_commit_hash=_sha(b"lc" + h.to_bytes(8, "big")),
            data_hash=_sha(b"data" + h.to_bytes(8, "big")),
            validators_hash=vh,
            next_validators_hash=vh,
            consensus_hash=_sha(b"consensus"),
            app_hash=_sha(b"app" + h.to_bytes(8, "big")),
            last_results_hash=_sha(b"results"),
            evidence_hash=b"",
            proposer_address=vals.validators[h % len(vals.validators)].address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, _sha(b"p" + h.to_bytes(8, "big"))))
        commit = make_commit(
            chain_id, h, 0, bid, vals, keys_by_addr,
            timestamp_ns=header.time_ns,
        )
        out.append(LightBlock(SignedHeader(header, commit), vals))
        last_bid = bid
    return out


def make_list_provider(blocks, chain_id: str = "light-chain"):
    """An in-memory light-block Provider over a prebuilt chain (height
    0 = tip), with a fetch counter — the serving-side fixture for the
    LightFleet tests."""
    from .light.provider import LightBlockNotFoundError, Provider

    class ListProvider(Provider):
        def __init__(self):
            self.blocks = {b.height: b for b in blocks}
            self.tip = max(self.blocks)
            self.fetches = 0

        def chain_id(self):
            return chain_id

        async def light_block(self, height):
            self.fetches += 1
            h = height or self.tip
            if h not in self.blocks:
                raise LightBlockNotFoundError(str(h))
            return self.blocks[h]

        async def report_evidence(self, ev):
            pass

    return ListProvider()


async def build_kvstore_chain(n_blocks: int, n_vals: int, chain_id: str = "ss-bench"):
    """Build an n_blocks kvstore chain through the real executor: returns
    (block_store, state_store, app_conns, genesis, keys_by_addr) with the
    app holding its periodic snapshots. Shared by the block-sync, crash-
    recovery and chaos-net tests and `statesync_fleet_scenario`."""
    from .abci.kvstore import KVStoreApp
    from .consensus.replay import Handshaker
    from .proxy import AppConns
    from .state.execution import BlockExecutor
    from .state.state import state_from_genesis
    from .state.store import StateStore
    from .store.blockstore import BlockStore
    from .store.db import MemDB
    from .types.genesis import GenesisDoc, GenesisValidator

    keys = det_priv_keys(n_vals)
    gvals = [GenesisValidator(k.pub_key(), 10, f"v{i}") for i, k in enumerate(keys)]
    genesis = GenesisDoc(
        chain_id=chain_id,
        initial_height=1,
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=gvals,
    )
    by_addr = {k.pub_key().address(): k for k in keys}
    app = KVStoreApp()
    conns = AppConns.local(app)
    bstore = BlockStore(MemDB())
    sstore = StateStore(MemDB())
    state = state_from_genesis(genesis)
    state = await Handshaker(sstore, state, bstore, genesis).handshake(conns)
    sstore.save(state)
    ex = BlockExecutor(sstore, conns.consensus, block_store=bstore)
    from .config import MempoolConfig
    from .mempool.pool import PriorityMempool

    mp = PriorityMempool(MempoolConfig(), conns.mempool)
    ex.mempool = mp
    commit = None
    for h in range(1, n_blocks + 1):
        if h % 3 == 1:
            await mp.check_tx(b"k%d=v%d" % (h, h))
        block, parts = ex.create_proposal_block(
            h, state, commit, state.validators.get_proposer().address
        )
        bid = block.block_id(parts.header)
        state, _ = await ex.apply_block(state, bid, block)
        commit = make_commit(
            chain_id, h, 0, bid, state.last_validators, by_addr,
            timestamp_ns=block.header.time_ns + 1,
        )
        bstore.save_block(block, parts, commit)
    return bstore, sstore, conns, genesis, by_addr


async def statesync_fleet_scenario(
    n_blocks: int,
    n_vals: int,
    n_joiners: int = 4,
    *,
    backfill_blocks: int | None = None,
    bootd_config=None,
    sync_timeout_s: float = 300.0,
) -> dict:
    """BootFleet in-process shape: ONE donor reactor (its BootD serving
    every joiner from the shared chunk cache) vs `n_joiners` concurrent
    cold joiners, bridged by routing pumps — the join-wave workload of
    the tier-1 BootFleet fixtures, without a live
    router mesh. Returns per-joiner sync times, the donor's BootD stats
    (cache amortization, sheds, store reads), and per-joiner join
    outcomes (a shed/failed joiner is an outcome, not a raise)."""
    import asyncio

    from .abci.kvstore import KVStoreApp
    from .p2p.peermanager import PeerStatus, PeerUpdate
    from .p2p.router import Channel
    from .p2p.types import Envelope
    from .proxy import AppConns
    from .state.store import StateStore
    from .statesync import (
        CHUNK_CHANNEL,
        LIGHT_BLOCK_CHANNEL,
        PARAMS_CHANNEL,
        SNAPSHOT_CHANNEL,
    )
    from .statesync import messages as ssm
    from .statesync.reactor import StateSyncReactor, SyncConfig
    from .store.blockstore import BlockStore
    from .store.db import MemDB

    src_bstore, src_sstore, src_conns, genesis, _keys = await build_kvstore_chain(
        n_blocks, n_vals
    )

    def channels() -> dict[int, Channel]:
        return {
            cid: Channel(cid, name, 5, ssm.encode_message, ssm.decode_message)
            for cid, name in (
                (SNAPSHOT_CHANNEL, "snapshot"),
                (CHUNK_CHANNEL, "chunk"),
                (LIGHT_BLOCK_CHANNEL, "lightblock"),
                (PARAMS_CHANNEL, "params"),
            )
        }

    src_ch = channels()
    server = StateSyncReactor(
        genesis.chain_id, src_conns, src_sstore, src_bstore,
        src_ch[SNAPSHOT_CHANNEL], src_ch[CHUNK_CHANNEL],
        src_ch[LIGHT_BLOCK_CHANNEL], src_ch[PARAMS_CHANNEL],
        asyncio.Queue(),
        bootd_config=bootd_config,
    )
    joiner_ch: dict[str, dict[int, Channel]] = {
        f"joiner-{i}": channels() for i in range(n_joiners)
    }
    clients: dict[str, StateSyncReactor] = {}
    apps: list[AppConns] = []
    stores: dict[str, BlockStore] = {}
    for name, chs in joiner_ch.items():
        app = AppConns.local(KVStoreApp(MemDB()))
        apps.append(app)
        bstore = BlockStore(MemDB())
        stores[name] = bstore
        q: asyncio.Queue = asyncio.Queue()
        clients[name] = StateSyncReactor(
            genesis.chain_id, app, StateStore(MemDB()), bstore,
            chs[SNAPSHOT_CHANNEL], chs[CHUNK_CHANNEL],
            chs[LIGHT_BLOCK_CHANNEL], chs[PARAMS_CHANNEL], q,
        )
        await q.put(PeerUpdate("server", PeerStatus.UP))

    async def pump_to_server(cid: int, name: str) -> None:
        src = joiner_ch[name][cid]
        while True:
            env = await src.out_q.get()
            await src_ch[cid].in_q.put(
                Envelope(env.channel_id, env.message, from_=name)
            )

    async def route_from_server(cid: int) -> None:
        # the server addresses every reply (`to=env.from_`); route it to
        # that joiner's channel — a broadcast (never sent today) fans out
        while True:
            env = await src_ch[cid].out_q.get()
            targets = [env.to] if env.to else list(joiner_ch)
            for t in targets:
                if t in joiner_ch:
                    await joiner_ch[t][cid].in_q.put(
                        Envelope(env.channel_id, env.message, from_="server")
                    )

    pumps = [
        asyncio.get_running_loop().create_task(pump_to_server(cid, name))
        for cid in src_ch
        for name in joiner_ch
    ] + [
        asyncio.get_running_loop().create_task(route_from_server(cid))
        for cid in src_ch
    ]
    await server.start()
    for c in clients.values():
        await c.start()
    loop = asyncio.get_running_loop()
    meta1 = src_bstore.load_block_meta(1)
    cfg = SyncConfig(
        trust_height=1,
        trust_hash=meta1.header.hash(),
        trust_period_ns=10 * 365 * 24 * 3600 * 10**9,
        backfill_blocks=backfill_blocks,
    )
    out: dict = {
        "n_joiners": n_joiners,
        "joined": 0,
        "join_errors": [],
        "time_to_synced_s": [],
        "headers_held": [],
        "elapsed_s": 0.0,
        "server_stats": {},
    }

    async def join_one(name: str) -> None:
        t0 = loop.time()
        try:
            state = await asyncio.wait_for(
                clients[name].sync(cfg), sync_timeout_s
            )
        except Exception as e:  # noqa: BLE001 — structured outcome
            out["join_errors"].append(f"{name}: {e!r}")
            return
        out["joined"] += 1
        out["time_to_synced_s"].append(round(loop.time() - t0, 4))
        held, h = 0, state.last_block_height
        while h >= 1 and stores[name].load_block_meta(h) is not None:
            held += 1
            h -= 1
        out["headers_held"].append(held)

    try:
        t0 = loop.time()
        await asyncio.gather(*(join_one(n) for n in clients))
        out["elapsed_s"] = round(loop.time() - t0, 4)
        out["server_stats"] = dict(server.bootd.stats)
        # backfill verification happens on the JOINERS' side (their
        # BootD counters), not the donor's
        out["joiner_backfill"] = {
            key: sum(c.bootd.stats[key] for c in clients.values())
            for key in (
                "backfill_heights", "backfill_sigs",
                "backfill_agg_heights", "backfill_batches",
            )
        }
        return out
    finally:
        for t in pumps:
            t.cancel()
        for c in clients.values():
            await c.stop()
        await server.stop()
        for app in apps:
            await app.stop()
        await src_conns.stop()


def make_vote(
    chain_id: str,
    key: ed25519.Ed25519PrivKey,
    index: int,
    height: int,
    round_: int,
    type_: SignedMsgType,
    block_id: BlockID,
    timestamp_ns: int = 1_700_000_000_000_000_000,
) -> Vote:
    sb = vote_sign_bytes(chain_id, type_, height, round_, block_id, timestamp_ns)
    return Vote(
        type=type_,
        height=height,
        round=round_,
        block_id=block_id,
        timestamp_ns=timestamp_ns,
        validator_address=key.pub_key().address(),
        validator_index=index,
        signature=key.sign(sb),
    )
