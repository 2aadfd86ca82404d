"""Driver `blocksync`: a full node catching up through block-sync.

The entry the window drives is `BlockSyncReactor` (start -> stop) wired as
`node.Node` wires it: the process VerifyHub acquired with the
VerifyHubConfig defaults, the reactor at DEFAULT_WINDOW over the real
block-sync channel and codec, a kvstore ABCI app, MemDB stores. Its peers
are stand-ins serving one seeded chain from memory with zero link delay,
as many as fill the pool's request window.

Closed loop, one pass by a fresh node from height 1, so no signature is
seen twice (the hub remembers verdicts). The window runs from
`reactor.start()` for --seconds; the rate is blocks applied over the
seconds that passed. The chain (the cell file's `blocks`) is sized to
outlast the window and the traced stretch after it at several times the
rate the program reads today; every result line says how much of it was
left (`chain_left_blocks`). A chain that ends inside an untraced window
closes it there and reads 1 left (the last block waits for a successor's
commit); in a traced run it raises `ChainEnded`: there is no stretch to
trace, and the cell file's `blocks` is what to lengthen.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from benchmark import fixtures, harness
from benchmark import reference as ref
from benchmark.harness import Check, say

END_TO_END = "blocksync_blocks_per_s"
#: the fixture is wire bytes and tables: built by `harness.FixtureChild`
FIXTURE = "child"


class ChainEnded(RuntimeError):
    """A traced run's chain ended before its traced stretch did."""


@dataclass
class Fixture:
    warm: fixtures.KVChain
    warm_bad_height: int  # the commit FOR this height is corrupted
    warm_bad_index: int
    chain: fixtures.KVChain | None = None  # the window's: handed over after the rest
    observed: dict = field(default_factory=dict)
    hub: object = None


def build(cfg: dict, cell: dict, seed: int):
    """The warm-up chain and its corruption first (the cell's warm-up starts
    on them), then the window's chain: what a run holds of a chain is its
    wire bytes and tables (`harness.assemble` for the protocol)."""
    p, v = cell["traffic"], cfg["validators"]
    t0 = time.perf_counter()
    warm = asyncio.run(fixtures.kvstore_chain(
        seed, "swarm", p["warmup_blocks"], v["count"], v["power"], p["txs_per_block"]))
    needed = ref.commit_verdict(warm.commit_data(1))[1]
    fx = Fixture(
        warm=warm,
        warm_bad_height=fixtures.seeded_index(seed, "sbadh", 8, min(40, p["warmup_blocks"] - 8)),
        warm_bad_index=fixtures.seeded_index(seed, "sbadi", needed - max(1, needed // 10), needed - 1),
    )
    say(f"blocksync: built {p['warmup_blocks']}-block warm-up chain ({v['count']} validators, "
        f"{p['txs_per_block']} txs a block, {needed} signatures reach > 2/3) in "
        f"{time.perf_counter() - t0:.1f}s; warm-up corruption: commit for height "
        f"{fx.warm_bad_height}, signature {fx.warm_bad_index}")
    yield fx
    t0 = time.perf_counter()
    chain = asyncio.run(fixtures.kvstore_chain(
        seed, "sync", p["blocks"], v["count"], v["power"], p["txs_per_block"]))
    say(f"blocksync: built {p['blocks']}-block chain in {time.perf_counter() - t0:.1f}s, "
        f"{sum(map(len, chain.wire.values()))} wire bytes")
    yield {"chain": chain}


def install(patches: harness.Patches, spans: harness.Spans, traced: bool) -> None:
    """`verify` around verify_commit_range as the reactor calls it (the
    reactor imported the name, so the wrapper goes on its module); in a
    traced run the host's share of each dispatch too."""
    from tendermint_tpu.blocksync import reactor

    def make(orig):
        def wrapped(*a, **kw):
            entries = a[1]
            with spans.span("verify", first=entries[0][2], n=len(entries)) as attrs:
                try:
                    return orig(*a, **kw)
                except Exception as e:
                    attrs["failed_index"] = getattr(e, "failed_index", 0)
                    raise

        return wrapped

    patches.wrap(reactor, "verify_commit_range", make)
    if traced:
        harness.host_prep_spans(patches, spans)


def acquire_hub(cfg: dict):
    """As node.Node does on start (node.py: acquire_hub with the node's
    [verify_hub] section), from the VerifyHubConfig defaults."""
    from tendermint_tpu.config import VerifyHubConfig
    from tendermint_tpu.crypto import verify_hub as vh

    c = VerifyHubConfig()
    stated = cfg["node"]["verify_hub"]
    for k, want in stated.items():
        if getattr(c, k) != want:
            raise RuntimeError(f"configuration states verify_hub.{k}={want}, "
                               f"the program's default is {getattr(c, k)}")
    return vh.acquire_hub(
        max_batch=c.max_batch, window_ms=c.window_ms, cache_size=c.cache_size,
        mesh_scale=c.mesh_scale, verifyd_sock=c.verifyd_sock,
    )


@dataclass
class Sync:
    """One run of the reactor against peer stand-ins."""

    height_at_close: int = 0
    final_height: int = 0
    app_hash: bytes = b""
    applied: list = field(default_factory=list)  # heights, in the order applied
    peer_errors: list = field(default_factory=list)  # (peer, text)
    refused: list = field(default_factory=list)  # heights the reactor punished
    stored_hashes: dict = field(default_factory=dict)
    t0: float = 0.0
    t1: float = 0.0
    ended_early: bool = False
    chain_left: int = 0  # chain length minus the store height where the run's reading ended


async def _sync(chain: fixtures.KVChain, cell: dict, seconds: float, spans: harness.Spans,
                *, bad: dict | None = None, traced: bool = False,
                trace: harness.DeviceTrace | None = None, on_close=lambda: None) -> Sync:
    """Start a fresh node's reactor against `peers` stand-ins and let it
    run for `seconds` (or to the chain's end). `bad` maps a height to a
    corrupted wire block, served ONCE, by whichever stand-in is asked
    first; the re-request after the refusal gets the honest block."""
    from tendermint_tpu.blocksync import BLOCKSYNC_CHANNEL
    from tendermint_tpu.blocksync import messages as bsm
    from tendermint_tpu.blocksync import reactor as reactor_mod
    from tendermint_tpu.p2p.peermanager import PeerStatus, PeerUpdate
    from tendermint_tpu.p2p.router import Channel
    from tendermint_tpu.p2p.types import Envelope

    p = cell["traffic"]
    if reactor_mod.DEFAULT_WINDOW != p["window"]:
        raise RuntimeError(f"cell states window {p['window']}, the reactor's "
                           f"DEFAULT_WINDOW is {reactor_mod.DEFAULT_WINDOW}")
    out = Sync()
    app, conns, bstore, state, ex = await fixtures.fresh_node(chain.genesis)
    ch = Channel(BLOCKSYNC_CHANNEL, "blocksync", 5, bsm.encode_message, bsm.decode_message)
    peer_q: asyncio.Queue = asyncio.Queue()
    reactor = reactor_mod.BlockSyncReactor(state, ex, bstore, ch, peer_q, active=True)
    peers = [f"peer{i}" for i in range(p["peers"])]
    bad = dict(bad or {})
    status = bsm.StatusResponse(chain.n_blocks, 1)

    apply_block = ex.apply_block

    async def applying(st, bid, block, **kw):
        if traced:
            with spans.span("apply"):
                res = await apply_block(st, bid, block, **kw)
        else:
            res = await apply_block(st, bid, block, **kw)
        out.applied.append(block.header.height)
        return res

    ex.apply_block = applying
    punish = reactor._punish

    async def punishing(height, *a, **kw):
        out.refused.append(height)
        return await punish(height, *a, **kw)

    reactor._punish = punishing

    async def serve():
        """Every stand-in answers from the same chain; the wire bytes are
        decoded on arrival, as the node's router does."""
        while True:
            env = await ch.out_q.get()
            msg = env.message
            targets = peers if env.broadcast else [env.to]
            for peer in targets:
                if isinstance(msg, bsm.StatusRequest):
                    reply = status
                elif isinstance(msg, bsm.BlockRequest):
                    raw = chain.wire.get(msg.height)
                    if raw is None:
                        continue
                    raw = bad.pop(msg.height, raw)
                    reply = ch.decode(raw)
                else:
                    continue
                await ch.in_q.put(Envelope(BLOCKSYNC_CHANNEL, reply, from_=peer))

    async def errors():
        """The router's part: a reported peer is disconnected."""
        while True:
            err = await ch.err_q.get()
            out.peer_errors.append((err.node_id, err.err))
            await peer_q.put(PeerUpdate(err.node_id, PeerStatus.DOWN))

    loop = asyncio.get_running_loop()
    tasks = [loop.create_task(serve()), loop.create_task(errors())]
    for peer in peers:
        await peer_q.put(PeerUpdate(peer, PeerStatus.UP))
    trace_seconds = float(p.get("trace_seconds", 5))
    out.t0 = time.perf_counter()
    await reactor.start()
    try:
        deadline = out.t0 + seconds
        while True:
            now = time.perf_counter()
            if now >= deadline:
                break
            if reactor.synced.is_set():
                out.ended_early = True
                break
            await asyncio.sleep(min(0.005, deadline - now))
        out.t1 = time.perf_counter()
        out.height_at_close = bstore.height()
        on_close()
        if trace is not None and not out.ended_early:
            # the traced stretch FOLLOWS the window (stopping a trace stalls
            # the host for seconds): the same sync simply goes on under it
            trace.start()
            stretch_end = time.perf_counter() + trace_seconds
            while time.perf_counter() < stretch_end and not reactor.synced.is_set():
                await asyncio.sleep(0.005)
            out.ended_early = reactor.synced.is_set()
            trace.stop()
        out.chain_left = chain.n_blocks - bstore.height()
    finally:
        if trace is not None:
            trace.stop()
        for t in tasks:
            t.cancel()
        await reactor.stop()
        # a range verify still running on its worker thread finishes on its
        # own; the counters are read only once it has
        while spans.open_count("verify"):
            await asyncio.sleep(0.01)
        await conns.stop()
    out.final_height = bstore.height()
    out.app_hash = app.app_hash
    for h in range(1, out.final_height + 1):
        meta = bstore.load_block_meta(h)
        out.stored_hashes[h] = meta.block_id.hash if meta else b""
    return out


def _bad_wire(chain: fixtures.KVChain, height: int, sig_index: int) -> dict:
    """The block AFTER `height`, with the commit FOR `height` (its
    LastCommit) corrupted, as the byzantine stand-in sends it."""
    import dataclasses

    from tendermint_tpu.blocksync import messages as bsm

    nxt = chain.block(height + 1)
    forged = dataclasses.replace(
        nxt, last_commit=fixtures.corrupt_commit(nxt.last_commit, sig_index))
    return {height + 1: bsm.encode_message(bsm.BlockResponse(forged))}


def _warm_shapes(fx: Fixture) -> list[str]:
    """Every (bucket, gb127) pair a SHORT range can take: since PR 39 a whole
    range is ONE hub group and one dispatch at the start-up's 8192/gb255; only
    one of <= `max_batch` rows (<= 5 commits: the re-fetch after a refusal, the
    chain's tail) takes its own rung, from the measured cut-off up to 512."""
    from tendermint_tpu.crypto import batch as cb

    items = []
    for h in range(1, 8):
        c = fx.warm.commit(h)
        n = ref.commit_verdict(fx.warm.commit_data(h))[1]
        for idx in range(n):
            items.append((fx.warm.vals.validators[idx].pub_key,
                          c.vote_sign_bytes(fx.warm.chain_id, idx),
                          c.signatures[idx].signature))
    warmed = []
    if not cb.tpu_verifier_available():
        return ["none: no device route in this process"]
    for bucket in (512, 256, 128, 64):
        if bucket < cb.MIN_TPU_BATCH or bucket > len(items):
            continue
        t0 = time.perf_counter()
        bv = cb.create_batch_verifier(items[0][0])
        for it in items[:bucket]:
            bv.add(*it)
        ok, _ = bv.verify()
        if not ok or bv.last_route != "tpu":
            raise RuntimeError(f"warm-up of bucket {bucket}: ok={ok} route={bv.last_route}")
        keys = len({it[0].bytes() for it in items[:bucket]})
        warmed.append(f"eq {bucket}/gb{127 if keys > 63 else 63} "
                      f"({time.perf_counter() - t0:.1f}s)")
    return warmed


def warmup(fx: Fixture, cfg: dict, cell: dict, spans: harness.Spans) -> list[str]:
    """Acquire the hub, warm every dispatch shape the cut-off lets a
    range reach, then drive a short sync of the warm-up chain (other
    chain ID and keys) through the reactor, one stand-in serving a
    corrupted commit that the node must refuse at exactly its height."""
    fx.hub = acquire_hub(cfg)
    shapes = _warm_shapes(fx)
    say(f"blocksync warm-up: shapes {shapes}")
    t0 = time.perf_counter()
    bad = _bad_wire(fx.warm, fx.warm_bad_height, fx.warm_bad_index)
    s = asyncio.run(_sync(fx.warm, cell, 120.0, spans, bad=bad))
    fx.observed["warm_peer_errors"] = s.peer_errors
    fx.observed["warm_final_height"] = s.final_height
    fx.observed["warm_app_hash_ok"] = (
        s.final_height >= fx.warm.n_blocks - 1
        and s.app_hash == ref.kv_state_hash(
            [tx for h in range(1, s.final_height + 1) for tx in fx.warm.txs_at[h]]))
    fx.observed["warm_refused"] = s.refused
    fx.observed["warm_applied"] = s.applied
    shapes.append("per-signature 512 (the refusal's attribution)")
    say(f"blocksync warm-up: synced {s.final_height}/{fx.warm.n_blocks} in "
        f"{time.perf_counter() - t0:.1f}s, refused heights {s.refused}, "
        f"app hash ok {fx.observed['warm_app_hash_ok']}")
    return shapes


@dataclass
class Window:
    sync: Sync
    elapsed: float
    t0: float
    t1: float
    units: int
    trace: harness.DeviceTrace | None = None

    @property
    def metrics(self) -> dict:
        return {END_TO_END: self.units / self.elapsed}

    @property
    def report(self) -> dict:
        """Beside the metrics, in every result line: the room the chain had
        left at the end of the traced stretch (of the window, untraced)."""
        return {"chain_left_blocks": self.sync.chain_left}


def window(fx: Fixture, cfg: dict, cell: dict, seconds: float,
           patches: harness.Patches, trace: harness.DeviceTrace | None,
           spans: harness.Spans, on_close=lambda: None) -> Window:
    s = asyncio.run(_sync(fx.chain, cell, seconds, spans, traced=trace is not None,
                          trace=trace, on_close=on_close))
    w = Window(sync=s, elapsed=s.t1 - s.t0, t0=s.t0, t1=s.t1,
               units=s.height_at_close, trace=trace)
    if trace is not None and s.ended_early:
        raise ChainEnded(
            f"{cell['name']}: the chain ended before the traced stretch did — its "
            f"{fx.chain.n_blocks} blocks (`traffic.blocks` of the cell's workload file) were "
            f"used up at {w.units / w.elapsed:.1f} blocks/s ({w.units} applied in the "
            f"{w.elapsed:.3f}s window, store height {s.final_height} at the end): nothing "
            f"left to trace. Lengthen `blocks`")
    say(f"blocksync window: {w.units} blocks applied in {w.elapsed:.3f}s "
        f"({'chain ended first' if s.ended_early else 'closed on time'}); "
        f"final height {s.final_height}, {s.chain_left} of the chain's {fx.chain.n_blocks} "
        f"blocks left, peer errors {s.peer_errors}")
    return w


def release(fx: Fixture) -> None:
    from tendermint_tpu.crypto import verify_hub as vh

    if fx.hub is not None:
        vh.release_hub()
        fx.hub = None


def compare(fx: Fixture, w: Window, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    """The timed path's own products against the plain reference: the
    verdict on every commit each range consumed, the heights applied in
    order, every stored block, the app hash, the signatures the hub was
    asked for against the signatures the > 2/3 rule needs, the warm-up's
    refusal — and that the device, not a host re-verify, served the
    window. Returns (checks, attempted, failed)."""
    s = w.sync
    ranges = [(r[3]["first"], r[3]["n"], r[3].get("failed_index"))
              for r in spans.select("verify") if r[1] >= w.t0]
    heights = sorted({h for first, n, _f in ranges for h in range(first, first + n)})
    verdicts = dict(zip(heights, ref.commit_verdicts(
        [fx.chain.commit_data(h) for h in heights])))
    mismatches = attempted = failed = needed = 0
    for first, n, failed_index in ranges:
        for i in range(n):
            v = verdicts[first + i]
            attempted += 1
            needed += v[1]
            if failed_index is None:
                mismatches += not v[0]
            elif i == failed_index:
                failed += 1
                mismatches += v[0]
    mismatches += len(s.peer_errors) + len(s.refused)  # the reference refuses nothing here
    order_faults = sum(1 for i, h in enumerate(s.applied) if h != i + 1)
    order_faults += abs(len(s.applied) - s.final_height)
    stored_bad = sum(
        1 for h in range(1, s.final_height + 1)
        if s.stored_hashes.get(h) != fx.chain.block_hash_at[h])
    want_hash = ref.kv_state_hash(
        [tx for h in range(1, s.final_height + 1) for tx in fx.chain.txs_at[h]])
    app_bad = int(s.app_hash != want_hash) + int(
        s.final_height > 0 and fx.chain.app_hash_at[s.final_height] != want_hash)
    asked = (d.get("hub.submitted", 0.0) + d.get("hub.cache_hits", 0.0)
             + d.get("hub.coalesced", 0.0))

    # warm-up: the reference refuses the corrupted commit, and only it
    bad_block = fx.warm.block(fx.warm_bad_height + 1)
    forged = fixtures.commit_data(
        fx.warm.chain_id,
        fixtures.corrupt_commit(bad_block.last_commit, fx.warm_bad_index), fx.warm.vals)
    ref_refuses = not ref.commit_verdict(forged)[0] and ref.commit_verdict(
        fx.warm.commit_data(fx.warm_bad_height))[0]
    warm_applied = fx.observed.get("warm_applied", [])
    warm_faults = (
        int(not ref_refuses)
        + int(fx.observed.get("warm_refused") != [fx.warm_bad_height])
        + int(not fx.observed.get("warm_app_hash_ok", False))
        + sum(1 for i, h in enumerate(warm_applied) if h != i + 1)
    )
    checks = [
        Check("verdict_mismatches", mismatches, 0),
        Check("apply_order_faults", order_faults, 0),
        Check("stored_mismatches", stored_bad, 0),
        Check("app_hash_mismatch", app_bad, 0),
        Check("sigs_asked_minus_needed", abs(asked - needed), 0),
        Check("warmup_refusal_faults", warm_faults, 0),
        Check("blocks_applied", w.units, 1, "min"),
    ] + harness.device_served_checks(d)
    return checks, attempted, failed
