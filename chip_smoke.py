#!/usr/bin/env python3
"""Chip smoke: the verify funnel's main path, once, on the attached TPU.

    python chip_smoke.py            # one chip (what the driver runs)
    python chip_smoke.py --chips 4  # ONLY the mesh-sharded path vs one device

One process, through the entry points a node uses, at the size users run
(BASELINE configs 1 and 3: 150 ed25519 validators, kvstore app):

  attach     crypto.batch's own start (tpu_verifier_available -> _probe_tpu:
             watchdogged attach, Pallas self-test, floor + 8192 warmup,
             measured CPU/TPU cutoff). Anything but a TPU is refused.
  range      108 commits x 150 validators = 16,200 signatures (two 8192
             chunks) through types.validation.verify_commit_range, the
             AdaptiveBatchVerifier and tpu.verify.verify_batch_eq: all
             valid -> all true; a few corrupted -> exactly those false;
             both bit for bit against Ed25519PubKey.verify_signature.
  mixed      a 20-validator committee of 10 ed25519 + 10 secp256k1 keys
             (BASELINE config 4's shape), as many commits as put the
             range's Edwards rows over the measured cut-off, through
             verify_commit_range and the AdaptiveBatchVerifier: the Edwards
             rows on route `tpu`, the ECDSA rows on the host lane (route
             `host-ecdsa`), none on `cpu`; valid -> accepted, one flipped
             row of each scheme -> refused at the first, the bitmap equal
             to each key's own host verify.
  blocksync  a seeded 300-block, 150-validator kvstore chain replayed
             through the real BlockSyncReactor to the app hash the chain
             was built with.
  net4       four node.Node validators over the memory transport to
             height 3 with the device probe live.

After the range, mixed and blocksync phases it asserts the DEVICE served them — the
production path re-verifies on the host after any device error, so a right
bitmap alone proves nothing about the chip: route "tpu", breaker never
opened, zero host re-verifies, zero degrade retries, active kind "tpu",
`field_mul_probe` free of `error`/`scan_error`.

Exit code 0 and a last stdout line
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
only when every phase passed. No TPU, a failed assertion or any exception:
traceback, non-zero exit, no result line. Data is made from --seed.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import sys
import threading
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_VALS = 150
#: commits per range: 16,200 signatures, two 8192-signature chunks
N_COMMITS = 108
N_BLOCKS = 300
N_CORRUPT = 5
MIXED_VALS = 20
NET_VALS = 4
NET_HEIGHT = 3
TXS_PER_BLOCK = 2

_T0 = time.monotonic()


def say(msg: str) -> None:
    print(f"[{time.monotonic() - _T0:7.1f}s] {msg}", flush=True)


class _CompileCounters:
    """Persistent-compile-cache hits/misses and backend compile seconds,
    from jax's own monitoring events."""

    def __init__(self):
        import jax.monitoring as mon

        self.hits = self.misses = self.compiles = 0
        self.compile_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def snapshot(self) -> dict:
        return {
            "persistent_cache_hits": self.hits,
            "persistent_cache_misses": self.misses,
            "backend_compiles": self.compiles,
            "backend_compile_s": round(self.compile_s, 1),
        }


def attach(want_chips: int) -> dict:
    """Bring the device up through the program's own start and refuse
    anything but `want_chips` TPU devices."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb

    if os.environ.get("TMTPU_DISABLE_TPU"):
        raise RuntimeError("TMTPU_DISABLE_TPU is set: the device path is off")
    t0 = time.monotonic()
    cb.tpu_verifier_available()  # kicks _probe_tpu on its daemon thread
    # the probe stamps the active kind right after its attach, BEFORE the
    # minutes of warmup: a CPU-only process is refused here, not after
    # compiling the kernels for the wrong backend
    while bt.ACTIVE["kind"] == "none" and cb._tpu_available is None:
        time.sleep(0.05)
    if bt.ACTIVE["kind"] != "tpu":
        raise RuntimeError(
            f"no TPU: the probe attached kind {bt.ACTIVE['kind']!r} "
            f"(telemetry: {bt.snapshot()})"
        )
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: jax.devices()[0].platform={dev.platform!r}")
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
    }
    say(f"attached in {time.monotonic() - t0:.1f}s: {device}")
    if device["count"] != want_chips:
        raise RuntimeError(f"need {want_chips} chip(s), jax sees {device['count']}")
    if not cb.tpu_wait_available():
        raise RuntimeError(f"device probe failed: {bt.snapshot()}")
    say(f"device available after {time.monotonic() - t0:.1f}s "
        f"(attach + Pallas self-test + floor warm-up + cutoff; MIN_TPU_BATCH={cb.MIN_TPU_BATCH})")
    # a node starts serving here while the probe thread still warms the
    # 8192 range shapes; the smoke waits for it, so that the phases' seconds
    # are not mixed with a background compile
    for t in threading.enumerate():
        if t.name == "tpu-probe":
            t.join()
    say(f"probe thread finished after {time.monotonic() - t0:.1f}s; "
        f"compile seconds {bt.snapshot()['compile_seconds']}")
    return device


def build_range(seed: int):
    """(vals, commits, items): N_COMMITS fully signed commits of an
    N_VALS-validator set, and the flat (pub, msg, sig) triples."""
    from tendermint_tpu import testing as tt

    chain_id = "smoke-chain"
    vals, keys = tt.make_validator_set(N_VALS, power=10, seed=b"smoke-%d" % seed)
    commits, items = [], []
    for h in range(1, N_COMMITS + 1):
        bid = tt.make_block_id(b"smoke-%d-%d" % (seed, h))
        commit = tt.make_commit(chain_id, h, 0, bid, vals, keys)
        commits.append((bid, commit))
        for idx, cs in enumerate(commit.signatures):
            items.append(
                (
                    vals.validators[idx].pub_key.bytes(),
                    commit.vote_sign_bytes(chain_id, idx),
                    cs.signature,
                )
            )
    return chain_id, vals, commits, items


def corrupt(items: list, seed: int) -> tuple[list, list[int]]:
    """Flip one bit in N_CORRUPT seeded signatures, spread over the range
    (both 8192-chunks get at least one)."""
    n = len(items)
    picks = sorted(
        {
            (int.from_bytes(hashlib.sha256(b"bad-%d-%d" % (seed, k)).digest()[:8], "big")
             % (n // N_CORRUPT)) + k * (n // N_CORRUPT)
            for k in range(N_CORRUPT)
        }
    )
    bad = list(items)
    for i in picks:
        pub, msg, sig = bad[i]
        bad[i] = (pub, msg, sig[:40] + bytes([sig[40] ^ 0x04]) + sig[41:])
    return bad, picks


def host_oracle(items: list):
    import numpy as np

    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey

    return np.array(
        [Ed25519PubKey(pub).verify_signature(msg, sig) for pub, msg, sig in items]
    )


def _adaptive_verify(items: list):
    """The production batch verifier (breaker, host re-verify, routes)."""
    import numpy as np

    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey

    bv = cb.create_batch_verifier(Ed25519PubKey(items[0][0]))
    for pub, msg, sig in items:
        bv.add(Ed25519PubKey(pub), msg, sig)
    ok, bitmap = bv.verify()
    return ok, np.array(bitmap), bv


def phase_range(seed: int) -> dict:
    import numpy as np

    from tendermint_tpu.crypto.tpu import verify as tpuv
    from tendermint_tpu.types import validation

    t0 = time.monotonic()
    chain_id, vals, commits, items = build_range(seed)
    bad_items, picks = corrupt(items, seed)
    want = host_oracle(items)
    want_bad = host_oracle(bad_items)
    assert want.all(), "host oracle rejected a freshly signed commit"
    assert list(np.flatnonzero(~want_bad)) == picks, "host oracle vs corruption"
    say(f"range: {len(items)} signatures built + host oracle in "
        f"{time.monotonic() - t0:.1f}s; corrupted indices {picks}")

    # the node's entry point (what block-sync calls per window)
    t1 = time.monotonic()
    validation.verify_commit_range(
        chain_id, [(vals, bid, c.height, c) for bid, c in commits]
    )
    say(f"range: verify_commit_range({len(commits)} commits) ok in "
        f"{time.monotonic() - t1:.2f}s")

    # the production verifier over all 16,200, valid then corrupted
    t1 = time.monotonic()
    ok, got, bv = _adaptive_verify(items)
    assert bv.last_route == "tpu", f"valid range ran on {bv.last_route!r}"
    assert ok and np.array_equal(got, want), "valid range: bitmap != host oracle"
    ok, got, bv = _adaptive_verify(bad_items)
    assert bv.last_route == "tpu", f"corrupted range ran on {bv.last_route!r}"
    assert not ok and np.array_equal(got, want_bad), (
        "corrupted range: bitmap != host oracle "
        f"(device false at {list(np.flatnonzero(~got))}, oracle at {picks})"
    )
    say(f"range: AdaptiveBatchVerifier valid+corrupted match the oracle bit "
        f"for bit in {time.monotonic() - t1:.2f}s (route tpu)")

    # and the raw kernel entry (no breaker, no host re-verify in the way)
    t1 = time.monotonic()
    assert np.array_equal(tpuv.verify_batch_eq(items), want)
    assert np.array_equal(tpuv.verify_batch_eq(bad_items), want_bad)
    say(f"range: tpu.verify.verify_batch_eq valid+corrupted match in "
        f"{time.monotonic() - t1:.2f}s")
    return {"signatures": len(items), "seconds": round(time.monotonic() - t0, 2)}


def phase_mixed(seed: int) -> dict:
    """One mixed commit range through the device route: Edwards rows
    `tpu`, ECDSA rows `host-ecdsa`, verdicts equal to the host's."""
    import dataclasses

    from tendermint_tpu import testing as tt
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.types import validation

    t0 = time.monotonic()
    chain_id = "smoke-mixed"
    vals, keys = tt.make_validator_set(MIXED_VALS, power=10, seed=b"smoke-mixed-%d" % seed,
                                       key_types=("ed25519", "secp256k1"))
    quorum = MIXED_VALS * 2 // 3 + 1  # equal powers, everyone signs
    schemes = [v.pub_key.TYPE for v in vals.validators[:quorum]]
    ed, ec = schemes.count("ed25519"), schemes.count("secp256k1")
    assert ed and ec, f"seed {seed}: the quorum holds one key type only: {schemes}"
    n_commits = -(-cb.MIN_TPU_BATCH // ed) + 1  # the Edwards rows clear the cut-off
    entries, items = [], []
    for h in range(1, n_commits + 1):
        bid = tt.make_block_id(b"smoke-mixed-%d-%d" % (seed, h))
        commit = tt.make_commit(chain_id, h, 0, bid, vals, keys)
        entries.append((vals, bid, h, commit))
        sign_bytes = commit.sign_bytes(chain_id)
        items += [(vals.validators[i].pub_key, sign_bytes(i), commit.signatures[i].signature)
                  for i in range(quorum)]

    before = {k: v[1] for k, v in bt.ROUTES.items()}
    validation.verify_commit_range(chain_id, entries)
    routes = {k: v[1] - before.get(k, 0) for k, v in bt.ROUTES.items() if v[1] != before.get(k, 0)}
    assert routes == {"tpu": n_commits * ed, "host-ecdsa": n_commits * ec}, (
        f"mixed range of {n_commits} commits ({ed} Edwards + {ec} ECDSA rows each, "
        f"cut-off {cb.MIN_TPU_BATCH}) was routed {routes}")

    # one flipped row of each scheme, the ECDSA one in the EARLIER commit
    def flip_bit(sig: bytes) -> bytes:
        return sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]

    def flip(entry, index):
        sigs = list(entry[3].signatures)
        sigs[index] = dataclasses.replace(sigs[index], signature=flip_bit(sigs[index].signature))
        return entry[:3] + (dataclasses.replace(entry[3], signatures=tuple(sigs)),)

    i_ec, i_ed = schemes.index("secp256k1"), schemes.index("ed25519")
    bad = list(entries)
    bad[1], bad[n_commits - 1] = flip(bad[1], i_ec), flip(bad[n_commits - 1], i_ed)
    try:
        validation.verify_commit_range(chain_id, bad)
        raise AssertionError("a mixed range with two flipped rows was accepted")
    except validation.InvalidCommitError as e:
        assert e.failed_index == 1 and f"index {i_ec}" in str(e), (e.failed_index, str(e))

    # the production verifier over the flat rows, against each key's own verify
    flat_bad = list(items)
    for row in (quorum + i_ec, (n_commits - 1) * quorum + i_ed):
        pk, msg, sig = flat_bad[row]
        flat_bad[row] = (pk, msg, flip_bit(sig))
    for label, rows in (("valid", items), ("corrupted", flat_bad)):
        want = [pk.verify_signature(msg, sig) for pk, msg, sig in rows]
        bv = cb.AdaptiveBatchVerifier()
        bv.add_many(rows)
        ok, got = bv.verify()
        assert bv.last_route == "mixed", f"{label} mixed rows ran on {bv.last_route!r}"
        assert got == want and ok is all(want), f"{label} mixed rows: bitmap != host verify"
    say(f"mixed: {n_commits} commits x ({ed} Edwards + {ec} ECDSA rows): range accepted on "
        f"routes {routes}; flipped rows refused at the first; bitmaps match the host's, in "
        f"{time.monotonic() - t0:.2f}s")
    return {"commits": n_commits, "edwards_rows": n_commits * ed, "ecdsa_rows": n_commits * ec,
            "seconds": round(time.monotonic() - t0, 2)}


async def _blocksync(seed: int) -> dict:
    """Replay a prebuilt kvstore chain through the REAL blocksync reactor
    (fetch -> range-batched verify -> ApplyBlock) over an in-process
    channel bridge; the peer is a stand-in that serves the source store."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.blocksync import BLOCKSYNC_CHANNEL
    from tendermint_tpu.blocksync import messages as bsm
    from tendermint_tpu.blocksync.reactor import BlockSyncReactor
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.p2p.peermanager import PeerStatus, PeerUpdate
    from tendermint_tpu.p2p.router import Channel
    from tendermint_tpu.p2p.types import Envelope
    from tendermint_tpu.proxy import AppConns
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.state.validation import median_time
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    chain_id = "smoke-bs"
    keys = tt.det_priv_keys(N_VALS, seed=b"smoke-bs-%d" % seed)
    genesis = GenesisDoc(
        chain_id=chain_id,
        initial_height=1,
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=[
            GenesisValidator(k.pub_key(), 10, f"v{i}") for i, k in enumerate(keys)
        ],
    )
    by_addr = {k.pub_key().address(): k for k in keys}

    async def fresh_node():
        app = KVStoreApp()
        conns = AppConns.local(app)
        bstore, sstore = BlockStore(MemDB()), StateStore(MemDB())
        state = await Handshaker(
            sstore, state_from_genesis(genesis), bstore, genesis
        ).handshake(conns)
        sstore.save(state)
        return app, conns, bstore, state, BlockExecutor(
            sstore, conns.consensus, block_store=bstore
        )

    # -- source chain: seeded txs in every block, so the app hash moves
    t0 = time.monotonic()
    src_app, src_conns, src_store, state, ex = await fresh_node()
    app_hash_at: dict[int, bytes] = {}
    commit = None
    for h in range(1, N_BLOCKS + 1):
        txs = tuple(
            b"k%d-%d-%d=v%d" % (seed, h, j, h * 31 + j) for j in range(TXS_PER_BLOCK)
        )
        time_ns = (
            state.last_block_time_ns
            if h == state.initial_height
            else median_time(commit, state.last_validators)
        )
        block = state.make_block(
            h, txs, commit, (), state.validators.get_proposer().address, time_ns
        )
        parts = block.make_part_set()
        bid = block.block_id(parts.header)
        state, _ = await ex.apply_block(state, bid, block)
        app_hash_at[h] = src_app.app_hash
        commit = tt.make_commit(
            chain_id, h, 0, bid, state.last_validators, by_addr,
            timestamp_ns=block.header.time_ns + 1,
        )
        src_store.save_block(block, parts, commit)
    build_s = time.monotonic() - t0
    say(f"blocksync: built {N_BLOCKS}-block x {N_VALS}-validator kvstore chain "
        f"in {build_s:.1f}s, final app hash {app_hash_at[N_BLOCKS].hex()[:16]}…")

    # -- target: fresh state, the real reactor
    app, conns, bstore, state, ex = await fresh_node()
    ch = Channel(
        BLOCKSYNC_CHANNEL, "blocksync", 5, bsm.encode_message, bsm.decode_message
    )
    peer_q: asyncio.Queue = asyncio.Queue()
    reactor = BlockSyncReactor(
        state, ex, bstore, ch, peer_q, window=N_COMMITS, active=True
    )

    async def serve_peer():
        while True:
            msg = (await ch.out_q.get()).message
            if isinstance(msg, bsm.StatusRequest):
                reply = bsm.StatusResponse(src_store.height(), src_store.base())
            elif isinstance(msg, bsm.BlockRequest):
                block = src_store.load_block(msg.height)
                if block is None:
                    continue
                reply = bsm.BlockResponse(block)
            else:
                continue
            await ch.in_q.put(Envelope(BLOCKSYNC_CHANNEL, reply, from_="peer0"))

    server = asyncio.get_running_loop().create_task(serve_peer())
    await peer_q.put(PeerUpdate("peer0", PeerStatus.UP))
    t0 = time.monotonic()
    await reactor.start()
    try:
        await asyncio.wait_for(reactor.synced.wait(), timeout=900)
    finally:
        sync_s = time.monotonic() - t0
        server.cancel()
        await reactor.stop()
        await conns.stop()
        await src_conns.stop()
    height = bstore.height()
    # the last block has no successor commit to verify it with
    assert height >= N_BLOCKS - 1, f"synced to {height} of {N_BLOCKS}"
    assert app.app_hash == app_hash_at[height], (
        f"app hash at {height}: replay {app.app_hash.hex()} != "
        f"source {app_hash_at[height].hex()}"
    )
    m = reactor.metrics
    say(f"blocksync: replayed {m['blocks_applied']} blocks ({m['sigs_verified']} "
        f"sigs, {m['ranges']} ranges) in {sync_s:.1f}s; app hash at {height} matches")
    return {
        "blocks": int(m["blocks_applied"]),
        "signatures": int(m["sigs_verified"]),
        "ranges": int(m["ranges"]),
        "build_s": round(build_s, 2),
        "seconds": round(sync_s, 2),
    }


async def _net4() -> dict:
    """Four full nodes (consensus + p2p router + VerifyHub) over the
    memory transport, device probe live. Four-vote batches sit below the
    measured cutoff and rightly stay on the host: heights must advance
    and the hub must see no verify error; routes are printed."""
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.consensus.harness import fast_config, make_genesis
    from tendermint_tpu.crypto import ed25519
    from tendermint_tpu.crypto.verify_hub import running_hub
    from tendermint_tpu.node import Node, NodeConfig
    from tendermint_tpu.p2p.memory import MemoryNetwork
    from tendermint_tpu.p2p.types import NodeAddress, node_id_from_pubkey
    from tendermint_tpu.privval import MockPV

    genesis, keys = make_genesis(NET_VALS, chain_id="smoke-net")
    memory = MemoryNetwork()
    nodes = []
    for i, key in enumerate(keys):
        node_key = ed25519.Ed25519PrivKey(bytes([0x40 + i]) * 32)
        transport = memory.create_transport(node_id_from_pubkey(node_key.pub_key()))
        nodes.append(
            Node(
                NodeConfig(consensus=fast_config(), moniker=f"n{i}"),
                genesis, KVStoreApp(), node_key, [transport],
                priv_validator=MockPV(key),
            )
        )
    t0 = time.monotonic()
    for n in nodes:
        await n.start()
    try:
        for i, a in enumerate(nodes):
            for b in nodes[i + 1:]:
                a.peer_manager.add_address(
                    NodeAddress(node_id=b.node_id, protocol="memory")
                )
        await asyncio.gather(*(n.wait_for_height(NET_HEIGHT, 120) for n in nodes))
        heights = [n.block_store.height() for n in nodes]
        hashes = {n.block_store.load_block(NET_HEIGHT - 1).hash() for n in nodes}
        hub = running_hub()
        assert hub is not None, "nodes are up but no VerifyHub is running"
        stats = hub.stats()
    finally:
        await asyncio.gather(*(n.stop() for n in nodes), return_exceptions=True)
    assert min(heights) >= NET_HEIGHT, f"heights {heights}"
    assert len(hashes) == 1, "nodes disagree on a committed block"
    assert stats["verify_errors"] == 0, f"hub verify_errors={stats['verify_errors']}"
    assert running_hub() is None, "VerifyHub not released after the last node stopped"
    dt = time.monotonic() - t0
    say(f"net4: {NET_VALS} validators reached heights {heights} in {dt:.1f}s; hub "
        f"dispatches={int(stats['dispatches'])} sigs={int(stats['dispatched_sigs'])} "
        f"cache_hits={int(stats['cache_hits'])} verify_errors=0")
    return {"heights": heights, "seconds": round(dt, 2)}


def assert_device_served(label: str, *, routed: bool = True) -> None:
    """The device, not a fallback, did the work so far. routed=False for
    a phase that drives the kernels below the AdaptiveBatchVerifier."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto.tpu import verify as tpuv

    snap = bt.snapshot()
    routes = snap["routes"]
    problems = []
    if snap["active_kind"] != "tpu":
        problems.append(f"active kind {snap['active_kind']!r}")
    if cb.tpu_breaker().opens or cb.tpu_breaker().state != "closed":
        problems.append(f"tpu breaker opened {cb.tpu_breaker().opens}x")
    if "cpu-fallback" in routes or snap["fallbacks"]:
        problems.append(f"host re-verifies after device errors: {routes} "
                        f"fallbacks={snap['fallbacks']}")
    if routed and not routes.get("tpu", [0, 0])[1]:
        problems.append(f"no signature was routed to the device: {routes}")
    for key in ("degrade_retries", "probe_errors", "pallas_probe_errors"):
        if snap[key]:
            problems.append(f"{key}={snap[key]}")
    if snap["mesh"]["degrade_transitions"]:
        problems.append(f"mesh degraded: {snap['mesh']}")
    for key in ("error", "scan_error"):
        if key in tpuv.field_mul_probe:
            problems.append(f"field_mul_probe[{key!r}]={tpuv.field_mul_probe[key]}")
    say(f"{label}: routes {routes} (MIN_TPU_BATCH={cb.MIN_TPU_BATCH})")
    assert not problems, f"{label}: the device did not serve the path: {problems}"


def phase_mesh(seed: int, n_chips: int) -> dict:
    """--chips N: the mesh-sharded dispatch the production selector picks
    on a multi-chip host, against the single-device kernel on devices[0]:
    same 16,200-signature range, valid and corrupted."""
    import jax
    import numpy as np

    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import mesh as mesh_mod
    from tendermint_tpu.crypto.tpu import verify as tpuv

    t0 = time.monotonic()
    _chain, _vals, _commits, items = build_range(seed)
    bad_items, picks = corrupt(items, seed)
    want, want_bad = host_oracle(items), host_oracle(bad_items)

    sel = tpuv._select_kernels(tpuv._MAX_BUCKET, 1)
    assert sel.devices is not None and len(sel.devices) == n_chips, (
        f"production selector did not pick the {n_chips}-device mesh: "
        f"{sel.devices}"
    )
    say(f"mesh: selector picked devices {[d.id for d in sel.devices]}, "
        f"bucket {sel.bucket}")
    # one plan a padded shape: a tail that pads to the chunk's rung takes the
    # chunk's plan, and under the measured gate a dispatch stays on one chip
    tail = tpuv._select_kernels(tpuv._MAX_BUCKET // 2 + 1, 1)
    small = tpuv._select_kernels(tpuv._SHARD_MIN_ROWS // 2, 1)
    assert (tail.bucket, tail.devices) == (sel.bucket, sel.devices), (tail.bucket, tail.devices)
    assert small.devices is None, f"a batch under the gate was sharded: {small.devices}"

    # where the sharded kernel's result actually lives: _shard_fill is
    # arithmetic, so ask the output array itself
    entries = [tpuv.resolve_ed25519(*it) for it in items[: sel.bucket]]
    bitmap, eq_ok = sel.kernel_eq(*tpuv.prepare_batch_eq(entries, pad_to=sel.bucket))
    placed = sorted(d.id for d in bitmap.sharding.device_set)
    say(f"mesh: sharded bitmap lives on devices {placed}, eq_ok={bool(eq_ok)}")
    assert len(placed) == n_chips and bool(eq_ok), (placed, bool(eq_ok))

    t1 = time.monotonic()
    sharded = tpuv.verify_batch_eq(items)
    info = tpuv.last_dispatch_info()
    sharded_bad = tpuv.verify_batch_eq(bad_items)
    info_bad = tpuv.last_dispatch_info()
    sharded_s = time.monotonic() - t1
    for tag, i in (("valid", info), ("corrupted", info_bad)):
        assert i and len(i["shards"]) == n_chips and all(i["shards"]), (
            f"{tag}: shard fill {i}"
        )
    say(f"mesh: sharded valid+corrupted in {sharded_s:.2f}s, shards {info['shards']} "
        f"on devices {info['devices']}")

    os.environ["TMTPU_NO_SHARDED"] = "1"  # the existing single-device pin
    try:
        t1 = time.monotonic()
        single = tpuv.verify_batch_eq(items)
        assert tpuv.last_dispatch_info() is None, "single-device run was sharded"
        single_bad = tpuv.verify_batch_eq(bad_items)
        single_s = time.monotonic() - t1
    finally:
        del os.environ["TMTPU_NO_SHARDED"]
    say(f"mesh: single-device valid+corrupted in {single_s:.2f}s "
        f"(incl. its cold compiles) on {jax.devices()[0]}")

    assert np.array_equal(sharded, single) and np.array_equal(sharded, want)
    assert np.array_equal(sharded_bad, single_bad)
    assert np.array_equal(sharded_bad, want_bad), (
        f"sharded false at {list(np.flatnonzero(~sharded_bad))}, oracle {picks}"
    )
    with mesh_mod._lock:
        tripped = [i for i, b in mesh_mod._breakers.items() if b.state != "closed"]
    assert not tripped and not bt.MESH["degrade_transitions"], (tripped, bt.MESH)
    assert not bt.BACKEND["degrade_retries"], bt.BACKEND
    say(f"mesh: sharded == single-device == host oracle on {len(items)} valid and "
        f"corrupted ({picks}); no mesh breaker opened; shard sigs {bt.SHARD_SIGS}")
    return {"signatures": len(items), "seconds": round(time.monotonic() - t0, 2)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=22)
    args = ap.parse_args(argv)

    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import verify as tpuv

    counters = _CompileCounters()
    device = attach(args.chips)
    say(f"compile cache dir: {os.environ.get('JAX_COMPILATION_CACHE_DIR') or tpuv.COMPILE_CACHE_DIR}"
        f" ({'JAX_COMPILATION_CACHE_DIR' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'in-checkout default'})")
    say(f"field_mul_probe: {json.dumps(tpuv.field_mul_probe)}")
    say(f"after probe: {json.dumps(counters.snapshot())}")

    phases: dict = {}
    if args.chips > 1:
        phases["mesh"] = phase_mesh(args.seed, args.chips)
    else:
        phases["range"] = phase_range(args.seed)
        assert_device_served("range")
        phases["mixed"] = phase_mixed(args.seed)
        assert_device_served("mixed")
        phases["blocksync"] = asyncio.run(_blocksync(args.seed))
        assert_device_served("blocksync")
        phases["net4"] = asyncio.run(_net4())
    assert_device_served("end", routed=args.chips == 1)

    say(f"telemetry: {json.dumps(bt.snapshot())}")
    say(f"compiles: {json.dumps(counters.snapshot())}")
    say(f"phases: {json.dumps(phases)}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:  # argparse
        rc = e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001 — any failure: traceback, non-zero, no result
        traceback.print_exc()
        sys.stdout.flush()
        sys.stderr.flush()
        rc = 1
    # the probe/hub daemon threads may still hold XLA: leave without
    # running interpreter teardown under them
    os._exit(rc)
