#!/usr/bin/env python3
"""Headline benchmark: commit signatures verified per second on a
150-validator chain (BASELINE.md config 1/3 — the block-sync verification
hot path).

Procedure:
  1. Build a 150-validator ed25519 set and a range of signed commits
     (the shape block-sync sees when replaying history).
  2. CPU baseline: single-threaded host verification of one commit's
     signatures (OpenSSL-backed — the stand-in for the reference's Go
     ed25519, which is not runnable in this image).
  3. TPU path: range-batched verification — all commits' signatures in one
     kernel launch (how blocksync batches ranges of historical commits),
     end-to-end including host sign-bytes construction and hashing.

The device is not optional: the run attaches the TPU first and exits
non-zero when there is none — no CPU fallback, no re-exec, no shrunken
CPU-sized workload. The validity bitmap is checked on both the all-valid
and the corrupted-signature path before any rate is reported, and a phase
that raises makes the whole run exit non-zero (after printing what did
run, with the failed phases named).

One process per chip: everything in the default run happens in THIS
process. The verifyd sidecar config starts a daemon that must own the
chip, so it runs only as `python bench.py verifyd`, from a parent that
never initialises JAX (ROADMAP S0).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Diagnostics go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time


def log(*a):
    print(*a, file=sys.stderr, flush=True)


#: Commits per TPU range at 150 validators: two full 8192-signature
#: chunks (verify_resolved's _MAX_BUCKET), so host prep of chunk 2
#: overlaps chunk 1's device execution. Used for BOTH the headline batch
#: and the blocksync window so the two benches measure the same shape.
TPU_RANGE_COMMITS = 2 * 8192 // 150  # 109


def require_chip() -> dict:
    """Attach the accelerator and describe it as JAX reports it. No TPU
    is an error: a device benchmark has nothing to say about a CPU."""
    import jax

    t0 = time.time()
    dev = jax.devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "attach_s": round(time.time() - t0, 3),
    }
    log(f"backend up after {device['attach_s']}s: {jax.devices()}")
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py needs a TPU; jax attached {device}")
    return device


def _build_commit_items(n_vals, n_commits, chain_id="bench-chain"):
    from tendermint_tpu import testing as tt

    vals, keys = tt.make_validator_set(n_vals, power=10)
    commits = []
    for h in range(1, n_commits + 1):
        bid = tt.make_block_id(b"block-%d" % h)
        commits.append((bid, tt.make_commit(chain_id, h, 0, bid, vals, keys)))
    items = []
    for _, commit in commits:
        for idx, cs in enumerate(commit.signatures):
            val = vals.validators[idx]
            items.append(
                (val.pub_key.bytes(), commit.vote_sign_bytes(chain_id, idx), cs.signature)
            )
    return vals, keys, commits, items


def kernel_breakdown(items: list) -> dict:
    """Stage-level timing of the batch-equation kernel on the live backend
    (decompress vs window scans vs Horner fold, plus a
    field-mul count and achieved-FLOP estimate). Each stage is jitted
    separately on the SAME padded batch; the deltas attribute the
    end-to-end time. Diagnostics only — production uses the fused kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tendermint_tpu.crypto.tpu import curve, msm
    from tendermint_tpu.crypto.tpu import verify as tpuv
    from tendermint_tpu.crypto.tpu.curve import Point

    # cap the stage-timing batch: the sub-stages are separate XLA
    # compiles, and 1024 is representative without risking the driver's
    # time budget on compile
    entries = [tpuv.resolve_ed25519(*it) for it in items[:1024]]
    b = tpuv._bucket(len(entries))
    ua_bytes, r_bytes, ga_digits, r_digits, zs_digits, s_valid, gidx = (
        tpuv.prepare_batch_eq(entries, pad_to=b)
    )
    gb = ua_bytes.shape[0]

    def timeit(fn, *args, reps=5):
        out = fn(*args)
        out = np.asarray(jax.tree.leaves(out)[0])  # compile + warm + sync
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        np.asarray(jax.tree.leaves(out)[0])  # sync
        return (time.perf_counter() - t0) / reps

    dec = jax.jit(
        lambda ab, rb: curve.decompress(
            jnp.concatenate([ab, rb], axis=0).astype(jnp.int32)
        )
    )
    t_dec = timeit(dec, ua_bytes, r_bytes)
    stacked, _ok = dec(ua_bytes, r_bytes)
    # A-side timed at gb+1 rows exactly as _kernel_eq runs it (the +1
    # base-point row keeps the length a power of two -> blocked-prefix
    # path; gb alone would fall back to the associative_scan branch and
    # time a different algorithm)
    bpt = curve.base_point(())
    a_pts = Point(
        *(
            jnp.concatenate([jnp.asarray(c[:gb]), bc[None]], axis=0)
            for c, bc in zip(stacked, bpt)
        )
    )
    r_pts = Point(*(jnp.asarray(c[gb : gb + b]) for c in stacked))
    ga_full = jnp.concatenate(
        [jnp.asarray(ga_digits), jnp.asarray(zs_digits)], axis=1
    ).astype(jnp.int32)

    msm_fn = jax.jit(msm.msm)
    t_msm_a = timeit(msm_fn, a_pts, ga_full)  # 32 windows, grouped + base row
    t_msm_r = timeit(msm_fn, r_pts, jnp.asarray(r_digits, jnp.int32))  # 16 windows
    t_full = timeit(
        jax.jit(tpuv._kernel_eq),
        ua_bytes, r_bytes, ga_digits, r_digits, zs_digits, s_valid, gidx,
    )

    # arithmetic accounting: point_add ≈ 9 field muls, double ≈ 8.
    # Per window: sort + blocked boundary prefixes (~M + 2M/16 + 256
    # adds) + 256-leaf collapse (~264 adds) + 255× multiply (7 dbl +
    # 7 add). 16 R-group windows at M=b, 32 A-group windows at M=gb+1;
    # Horner fold adds 8 dbl + 1 add per window.
    def window_adds(m):
        return m + 2 * m // 16 + 256 + 264 + 14

    adds = 16 * window_adds(b) + 32 * window_adds(gb + 1)
    fmuls = adds * 9 + 48 * (8 * 8 + 9)
    # one field mul (GEMM path) routes 32*32*32 ≈ 32.8k f32 MACs through
    # the MXU per element-pair after batching
    flops = fmuls * 2 * 32 * 32 * 32
    bd = {
        "batch": b,
        "groups": gb,
        "decompress_ms": round(t_dec * 1e3, 2),
        "msm_a32_ms": round(t_msm_a * 1e3, 2),
        "msm_r16_ms": round(t_msm_r * 1e3, 2),
        "fused_total_ms": round(t_full * 1e3, 2),
        "field_muls_est": fmuls,
        "achieved_tflops_est": round(flops / t_full / 1e12, 3),
    }
    log(f"kernel breakdown: {bd}")
    if tpuv.field_mul_probe:
        bd["field_mul_probe"] = dict(tpuv.field_mul_probe)
        log(f"field-mul A/B probe: {tpuv.field_mul_probe}")
    return bd


def bench_mixed_commit(n_vals: int, n_commits: int) -> float:
    """BASELINE config 4: mixed ed25519 + secp256k1 validator set through
    verify_commit_light (reference types/validator_set.go VerifyCommitLight
    with a heterogeneous key set). Returns sigs/sec."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.types import validation

    chain_id = "mixed-bench"
    vals, keys = tt.make_validator_set(
        n_vals, power=10, key_types=("ed25519", "secp256k1")
    )
    pairs = []
    for h in range(1, n_commits + 1):
        bid = tt.make_block_id(b"mixed-%d" % h)
        pairs.append((bid, tt.make_commit(chain_id, h, 0, bid, vals, keys)))
    t0 = time.perf_counter()
    total = 0
    for bid, commit in pairs:
        validation.verify_commit_light(chain_id, vals, bid, commit.height, commit)
        total += sum(1 for cs in commit.signatures if cs.is_commit())
    dt = time.perf_counter() - t0
    rate = total / dt
    log(
        f"mixed-key commit: {total} sigs over {n_commits} commits in {dt:.2f}s "
        f"-> {rate:,.1f} sigs/s"
    )
    return rate


def bench_statesync(n_blocks: int, n_vals: int) -> float:
    """BASELINE config 5: statesync snapshot restore + backfill commit
    verification (reference internal/statesync/reactor.go:348-369 shape,
    in-process). Returns backfilled+verified blocks/sec."""
    import asyncio

    from tendermint_tpu.testing import statesync_restore_scenario

    t0 = time.perf_counter()
    n_verified = asyncio.run(statesync_restore_scenario(n_blocks, n_vals))
    dt = time.perf_counter() - t0
    rate = n_verified / dt
    log(
        f"statesync: restored + backfilled {n_verified} blocks in {dt:.2f}s "
        f"-> {rate:,.1f} blocks/s"
    )
    return rate


def bench_light_client(n_headers: int, n_vals: int) -> float:
    """BASELINE config 2: sequential VerifyAdjacent over a chain of signed
    headers (reference light/client_benchmark_test.go shape), every commit
    going through the real verify_commit_light -> batch verifier path.
    Returns headers/sec."""
    import time as _t

    from tendermint_tpu import testing as tt
    from tendermint_tpu.crypto.hashes import sha256
    from tendermint_tpu.light import verifier
    from tendermint_tpu.light.types import LightBlock, SignedHeader
    from tendermint_tpu.types.block import BlockID, Header, PartSetHeader

    chain_id = "light-bench"
    vals, keys = tt.make_validator_set(n_vals, power=10)
    vh = vals.hash()
    t0 = _t.perf_counter()
    blocks = []
    base_ts = 1_700_000_000_000_000_000
    prev_hash = sha256(b"genesis")
    for h in range(1, n_headers + 1):
        hdr = Header(
            chain_id=chain_id,
            height=h,
            time_ns=base_ts + h * 1_000_000_000,
            last_block_id=BlockID(prev_hash, PartSetHeader(1, sha256(b"pp"))),
            data_hash=sha256(b"data-%d" % h),
            validators_hash=vh,
            next_validators_hash=vh,
            consensus_hash=sha256(b"consensus"),
            app_hash=sha256(b"app-%d" % h),
            last_results_hash=sha256(b"res"),
            proposer_address=vals.validators[h % n_vals].address,
        )
        bid = BlockID(hdr.hash(), PartSetHeader(1, sha256(b"parts-%d" % h)))
        commit = tt.make_commit(
            chain_id, h, 0, bid, vals, keys, timestamp_ns=hdr.time_ns
        )
        blocks.append(LightBlock(SignedHeader(hdr, commit), vals))
        prev_hash = hdr.hash()
    log(f"light: built {n_headers} signed headers in {_t.perf_counter()-t0:.1f}s")

    period = 10 * 365 * 24 * 3600 * 10**9
    now_ns = base_ts + (n_headers + 10) * 1_000_000_000
    t0 = _t.perf_counter()
    trusted = verifier.verify_adjacent_chain(
        chain_id, blocks[0], blocks[1:], period, now_ns
    )
    assert trusted.height == n_headers
    dt = _t.perf_counter() - t0
    rate = (n_headers - 1) / dt
    log(f"light: verified {n_headers-1} adjacent headers in {dt:.2f}s -> {rate:,.1f} headers/s")
    return rate


async def _bench_blocksync_async(n_blocks: int, n_vals: int, window: int) -> float:
    """BASELINE config 3: replay a prebuilt kvstore chain through the REAL
    blocksync reactor (fetch -> range-batched verify -> ApplyBlock) over an
    in-process channel bridge. Returns blocks/sec."""
    import asyncio
    import time as _t

    from tendermint_tpu import testing as tt
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.blocksync import BLOCKSYNC_CHANNEL
    from tendermint_tpu.blocksync import messages as bsm
    from tendermint_tpu.blocksync.reactor import BlockSyncReactor
    from tendermint_tpu.consensus.harness import make_genesis
    from tendermint_tpu.p2p.peermanager import PeerStatus, PeerUpdate
    from tendermint_tpu.p2p.router import Channel
    from tendermint_tpu.proxy import AppConns
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.db import MemDB
    from tendermint_tpu.testing import det_priv_keys
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    # genesis with n_vals validators
    keys = det_priv_keys(n_vals)
    gvals = [GenesisValidator(k.pub_key(), 10, f"v{i}") for i, k in enumerate(keys)]
    genesis = GenesisDoc(
        chain_id="bs-bench",
        initial_height=1,
        genesis_time_ns=1_700_000_000_000_000_000,
        validators=gvals,
    )
    by_addr = {k.pub_key().address(): k for k in keys}

    async def build_source():
        app = KVStoreApp()
        conns = AppConns.local(app)
        bstore = BlockStore(MemDB())
        sstore = StateStore(MemDB())
        state = state_from_genesis(genesis)
        from tendermint_tpu.consensus.replay import Handshaker

        state = await Handshaker(sstore, state, bstore, genesis).handshake(conns)
        sstore.save(state)
        ex = BlockExecutor(sstore, conns.consensus, block_store=bstore)
        commit = None
        t0 = _t.perf_counter()
        for h in range(1, n_blocks + 1):
            block, parts = ex.create_proposal_block(
                h, state, commit, state.validators.get_proposer().address
            )
            bid = block.block_id(parts.header)
            state, _ = await ex.apply_block(state, bid, block)
            commit = tt.make_commit(
                "bs-bench", h, 0, bid, state.last_validators, by_addr,
                timestamp_ns=block.header.time_ns + 1,
            )
            bstore.save_block(block, parts, commit)
        log(f"blocksync: built {n_blocks}-block chain in {_t.perf_counter()-t0:.1f}s")
        return bstore, conns

    src_store, src_conns = await build_source()

    # target node: fresh state, real reactor
    app = KVStoreApp()
    conns = AppConns.local(app)
    bstore = BlockStore(MemDB())
    sstore = StateStore(MemDB())
    state = state_from_genesis(genesis)
    from tendermint_tpu.consensus.replay import Handshaker

    state = await Handshaker(sstore, state, bstore, genesis).handshake(conns)
    sstore.save(state)
    ex = BlockExecutor(sstore, conns.consensus, block_store=bstore)

    ch = Channel(
        BLOCKSYNC_CHANNEL, "blocksync", 5, bsm.encode_message, bsm.decode_message
    )
    peer_q: asyncio.Queue = asyncio.Queue()
    reactor = BlockSyncReactor(
        state, ex, bstore, ch, peer_q, window=window, active=True
    )

    async def serve_peer():
        """Answer the reactor's outbound envelopes from the source store
        (the in-process stand-in for a remote peer's reactor)."""
        while True:
            env = await ch.out_q.get()
            msg = env.message
            from tendermint_tpu.p2p.types import Envelope

            if isinstance(msg, bsm.StatusRequest):
                await ch.in_q.put(
                    Envelope(
                        BLOCKSYNC_CHANNEL,
                        bsm.StatusResponse(src_store.height(), src_store.base()),
                        from_="peer0",
                    )
                )
            elif isinstance(msg, bsm.BlockRequest):
                block = src_store.load_block(msg.height)
                if block is not None:
                    await ch.in_q.put(
                        Envelope(
                            BLOCKSYNC_CHANNEL,
                            bsm.BlockResponse(block),
                            from_="peer0",
                        )
                    )

    server = asyncio.get_running_loop().create_task(serve_peer())
    await peer_q.put(PeerUpdate("peer0", PeerStatus.UP))
    t0 = _t.perf_counter()
    await reactor.start()
    await asyncio.wait_for(reactor.synced.wait(), timeout=3600)
    dt = _t.perf_counter() - t0
    server.cancel()
    await reactor.stop()
    await conns.stop()
    await src_conns.stop()
    applied = reactor.metrics["blocks_applied"]
    sigs = reactor.metrics["sigs_verified"]
    assert bstore.height() >= n_blocks - 1, (bstore.height(), n_blocks)
    rate = applied / dt
    log(
        f"blocksync: applied {applied} blocks ({sigs} sigs verified, "
        f"{reactor.metrics['ranges']} ranges) in {dt:.2f}s -> {rate:,.1f} blocks/s"
    )
    return rate


def bench_blocksync(n_blocks: int, n_vals: int, window: int) -> float:
    import asyncio

    return asyncio.run(_bench_blocksync_async(n_blocks, n_vals, window))


def bench_crash_recovery(n_heights: int = 400, msgs_per_height: int = 20) -> dict:
    """crash_recovery config: WAL replay throughput after a seeded crash.
    Build a WAL of `n_heights` heights (message records + fsync'd
    end-height markers) through the chaos-fs layer, tear the un-fsynced
    tail mid-record at a simulated crash, then measure (a) the open-time
    repair (truncate to the last whole record, rotate damaged tail
    aside) and (b) replay rate in heights/sec and records/sec — the
    downtime a validator spends between restart and first vote."""
    import shutil
    import tempfile
    import time as _t

    from tendermint_tpu.consensus.wal import KIND_END_HEIGHT, WAL
    from tendermint_tpu.libs.chaosfs import ChaosFS, ChaosFSConfig

    d = tempfile.mkdtemp(prefix="benchwal-")
    try:
        fs = ChaosFS(ChaosFSConfig(seed=9, torn_write_rate=1.0))
        wal = WAL(d, fs=fs)
        payload = b"\x12\x40" + b"\xab" * 126  # ~128B opaque consensus msg
        for h in range(1, n_heights + 1):
            for _ in range(msgs_per_height):
                wal.write(payload)
            wal.write_end_height(h)  # fsync: the durable watermark
        for _ in range(msgs_per_height):
            wal.write(payload)  # un-fsynced tail, torn by the crash
        fs.halt()
        wal.close()
        fs.simulate_crash()

        t0 = _t.perf_counter()
        wal2 = WAL(d, fs=fs)  # open-time repair
        repair_dt = _t.perf_counter() - t0
        t0 = _t.perf_counter()
        n_recs = heights = 0
        for rec in wal2.iter_records():
            n_recs += 1
            if rec.kind == KIND_END_HEIGHT:
                heights = rec.height
        replay_dt = _t.perf_counter() - t0
        wal2.close()
        out = {
            "replay_heights_per_s": round(heights / replay_dt, 1),
            "replay_records_per_s": round(n_recs / replay_dt, 1),
            "repair_ms": round(repair_dt * 1e3, 2),
            "repaired_files": len(wal2.last_repair),
            "heights": heights,
            "records": n_recs,
        }
        log(
            f"crash recovery: repaired {out['repaired_files']} file(s) in "
            f"{out['repair_ms']}ms, replayed {heights} heights "
            f"({n_recs} records) in {replay_dt:.3f}s -> "
            f"{out['replay_heights_per_s']:,.1f} heights/s"
        )
        assert heights == n_heights, (heights, n_heights)
        return out
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_chaos_soak(sizes: tuple = (4, 50)) -> dict:
    """chaos_soak config: the robustness trajectory MEASURED, not
    asserted — blocks/s and time-to-recover per named fault scenario
    (consensus/scenarios.py) at 4 and 50 validators, over REAL routers +
    ChaosTransport (RouterNet). BOUNDED, structured outcomes (the
    multichip discipline): every run carries the scenario engine's own
    liveness-watchdog deadline plus an outer asyncio timeout, and a
    wedge/timeout is a record, never a hang. The committee scale is wall
    clock, so 50-validator rows run a trimmed scenario list with a
    height-2 target."""
    import asyncio

    from tendermint_tpu.consensus import scenarios as sc

    seed = int(os.environ.get("TMTPU_BENCH_SOAK_SEED", "7") or 7)
    out: dict = {"seed": seed, "runs": []}
    for n_vals in sizes:
        small = n_vals <= 8
        names = (
            list(sc.SCENARIOS)
            if small
            else [
                "baseline",
                "lossy_links",
                "corrupt_wire",
                "asym_partition",
                "full_taxonomy",
            ]
        )
        target = 3 if small else 2
        timeout_s = 75.0 if small else 300.0
        for name in names:
            t0 = time.perf_counter()

            async def one(_name=name, _n=n_vals, _target=target, _to=timeout_s):
                return await sc.run_scenario(
                    _name,
                    n_vals=_n,
                    target_height=_target,
                    seed=seed,
                    timeout_s=_to,
                    stall_s=25.0 if small else 90.0,
                    time_scale=1.0 if small else 4.0,
                    degree=8,
                )

            try:
                res = asyncio.run(
                    asyncio.wait_for(one(), timeout_s + 60.0)
                ).as_dict()
            except Exception as e:  # noqa: BLE001 — structured outcome
                res = {
                    "scenario": name,
                    "n_vals": n_vals,
                    "outcome": f"error: {e!r}"[:200],
                }
            res["wall_s"] = round(time.perf_counter() - t0, 2)
            out["runs"].append(res)
            rec = res.get("recover_s")
            log(
                f"chaos_soak {n_vals:>3}v {name:<18} "
                f"{res.get('outcome', '?'):<7} "
                f"{res.get('blocks_per_s', 0)} blk/s "
                f"recover={'-' if rec is None else f'{rec}s'} "
                f"wall={res['wall_s']}s"
            )
    ok = [r for r in out["runs"] if r.get("outcome") == "ok"]
    out["ok_runs"] = len(ok)
    out["total_runs"] = len(out["runs"])
    return out


def bench_wiregen(soak_vals: int = 50) -> dict:
    """wiregen config: the compiled hot codec A/B'd against the
    interpreted codec it was generated from. Two halves:

      * per-family encode/decode frames/s, paired-interleaved: each rep
        times interpreted then generated back-to-back in the same
        window and the best rep wins, so shared-host steal lands on
        both sides instead of skewing the ratio;
      * chaos_soak blocks/s with the codec flipped — the same seeded
        baseline scenario at `soak_vals` validators, run once per
        codec, nets built AFTER the `use_wiregen` flip so every node
        dispatches through the codec under test.

    Pure host work; the device is not on this path."""
    import asyncio

    import tendermint_tpu.types.block as blk
    from tendermint_tpu.consensus import messages as cm
    from tendermint_tpu.consensus import wire_gen as wg
    from tendermint_tpu.crypto.merkle import Proof
    from tendermint_tpu.types.block import BlockID, PartSetHeader
    from tendermint_tpu.types.keys import BLOCK_PART_SIZE, SignedMsgType
    from tendermint_tpu.types.part_set import Part
    from tendermint_tpu.types.vote import Vote

    bid = BlockID(b"\xab" * 32, PartSetHeader(4, b"\xcd" * 32))

    def _vote(i: int) -> Vote:
        return Vote(
            type=SignedMsgType.PREVOTE,
            height=1000 + i,
            round=2,
            block_id=bid,
            timestamp_ns=1_700_000_000_000_000_000 + i,
            validator_address=bytes([i % 256]) * 20,
            validator_index=i,
            signature=bytes([i % 256]) * 64,
        )

    def _soak_part() -> cm.BlockPartMessage:
        # the shape chaos_soak actually gossips: a single-part block
        # (50-sig commit + a few txs), whose one-leaf proof has 0 aunts
        sigs = tuple(
            blk.CommitSig(
                flag=blk.BLOCK_ID_FLAG_COMMIT,
                validator_address=bytes([i % 256]) * 20,
                timestamp_ns=1_700_000_000_000_000_000 + i,
                signature=bytes([i % 256]) * 64,
            )
            for i in range(50)
        )
        hdr = blk.Header(
            chain_id="soak",
            height=3,
            time_ns=1_700_000_000_000_000_000,
            last_block_id=bid,
            proposer_address=b"\x01" * 20,
            validators_hash=b"\x02" * 32,
            next_validators_hash=b"\x02" * 32,
            app_hash=b"\x03" * 32,
        )
        block = blk.Block(
            header=hdr,
            txs=(b"tx-aaaa", b"tx-bbbb"),
            last_commit=blk.Commit(
                height=2, round=0, block_id=bid, signatures=sigs
            ),
        )
        return cm.BlockPartMessage(3, 0, block.make_part_set().parts[0])

    families = {
        "Vote": (cm.VoteMessage(_vote(7)), 3000),
        "VoteBatch[64]": (
            cm.VoteBatchMessage(tuple(_vote(i) for i in range(64))),
            200,
        ),
        "HasVote": (cm.HasVoteMessage(1000, 2, SignedMsgType.PREVOTE, 7), 5000),
        "BlockPart[soak]": (_soak_part(), 1000),
        "BlockPart[64KiB]": (
            cm.BlockPartMessage(
                9,
                1,
                Part(
                    3,
                    bytes(range(256)) * (BLOCK_PART_SIZE // 256),
                    Proof(16, 3, b"\x11" * 32, tuple(b"\x22" * 32 for _ in range(4))),
                ),
            ),
            400,
        ),
    }

    def _paired_best(fa, fb, arg, iters, reps=12):
        best_a = best_b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                fa(arg)
            t1 = time.perf_counter()
            for _ in range(iters):
                fb(arg)
            t2 = time.perf_counter()
            best_a = min(best_a, (t1 - t0) / iters)
            best_b = min(best_b, (t2 - t1) / iters)
        return best_a, best_b

    # warm the interpreter/caches before the first paired window
    warm = cm.encode_message_py(families["BlockPart[soak]"][0])
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < 0.5:
        cm.decode_message_py(warm)
        wg.decode_message(warm)

    out: dict = {"families": {}}
    for name, (msg, iters) in families.items():
        frame = cm.encode_message_py(msg)
        assert frame == wg.encode_message(msg), f"{name}: A/B bytes differ"
        ei, eg = _paired_best(
            cm.encode_message_py, wg.encode_message, msg, iters
        )
        di, dg = _paired_best(
            cm.decode_message_py, wg.decode_message, frame, iters
        )
        row = {
            "frame_bytes": len(frame),
            "enc_interp_per_s": round(1.0 / ei, 1),
            "enc_gen_per_s": round(1.0 / eg, 1),
            "enc_speedup": round(ei / eg, 2),
            "dec_interp_per_s": round(1.0 / di, 1),
            "dec_gen_per_s": round(1.0 / dg, 1),
            "dec_speedup": round(di / dg, 2),
        }
        out["families"][name] = row
        log(
            f"wiregen {name:<16} enc {row['enc_speedup']:>5.2f}x "
            f"dec {row['dec_speedup']:>5.2f}x "
            f"({row['dec_gen_per_s']:,.0f} dec/s gen)"
        )

    # -- chaos_soak blocks/s with the codec flipped -----------------------
    if os.environ.get("TMTPU_BENCH_WIREGEN_SOAK") != "0":
        from tendermint_tpu.consensus import scenarios as sc

        seed = int(os.environ.get("TMTPU_BENCH_SOAK_SEED", "7") or 7)
        was = cm.wiregen_active()
        soak: dict = {"n_vals": soak_vals, "seed": seed, "scenario": "baseline"}
        try:
            for label, enabled in (("interpreted", False), ("generated", True)):
                cm.use_wiregen(enabled)

                async def one(_n=soak_vals):
                    return await sc.run_scenario(
                        "baseline",
                        n_vals=_n,
                        target_height=2,
                        seed=seed,
                        timeout_s=300.0,
                        stall_s=90.0,
                        time_scale=4.0,
                        degree=8,
                    )

                t0 = time.perf_counter()
                try:
                    res = asyncio.run(
                        asyncio.wait_for(one(), 360.0)
                    ).as_dict()
                except Exception as e:  # noqa: BLE001 — structured outcome
                    res = {"outcome": f"error: {e!r}"[:200]}
                res["wall_s"] = round(time.perf_counter() - t0, 2)
                soak[label] = res
                log(
                    f"wiregen soak[{label}] {res.get('outcome', '?')} "
                    f"{res.get('blocks_per_s', 0)} blk/s "
                    f"wall={res['wall_s']}s"
                )
            bi = soak.get("interpreted", {}).get("blocks_per_s") or 0
            bg = soak.get("generated", {}).get("blocks_per_s") or 0
            soak["soak_speedup"] = round(bg / bi, 2) if bi else None
        finally:
            cm.use_wiregen(was)
        out["chaos_soak_ab"] = soak
    return out


def bench_merkle(soak_vals: int = 50) -> dict:
    """merkle config: the HashHub's level-order batched tree builder
    A/B'd against the scalar recursive reference. Three halves:

      * leaves/s at 64 / 1k / 16k leaves (250-byte leaves — the tx
        shape), paired-interleaved best-of-reps like extra.wiregen:
        scalar recursive vs batched level-order (CPU), plus the device
        bucket route when TMTPU_HASH_TPU=1;
      * block-hash/s over a realistic header (14 cdc-encoded fields +
        50-sig commit root), memoization stripped per rep so the tree
        build itself is what's timed;
      * chaos_soak blocks/s with `use_hashhub` flipped — the same
        seeded baseline scenario at `soak_vals` validators once per
        builder.

    The CPU half IS the acceptance number (≥1.5× at 1024 leaves):
    batching amortizes Python frames the way VoteBatch amortized
    envelopes; the device half only engages when explicitly enabled."""
    import asyncio
    from dataclasses import replace as _dc_replace

    import tendermint_tpu.types.block as blk
    from tendermint_tpu.crypto import hash_hub, merkle
    from tendermint_tpu.types.block import BlockID, PartSetHeader

    def _paired_best(fa, fb, reps=9):
        best_a = best_b = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fa()
            t1 = time.perf_counter()
            fb()
            t2 = time.perf_counter()
            best_a = min(best_a, t1 - t0)
            best_b = min(best_b, t2 - t1)
        return best_a, best_b

    out: dict = {"leaves": {}}
    device_on = False
    try:
        from tendermint_tpu.crypto.tpu import sha256 as dev_sha

        device_on = dev_sha.device_enabled()
        if device_on:
            dev_sha.warmup()  # compile outside the timed windows
    except Exception as e:  # noqa: BLE001 — device half is optional
        log(f"merkle device warmup failed: {e!r}")
        device_on = False

    for n in (64, 1024, 16384):
        leaves = [bytes([i % 256, (i >> 8) % 256]) * 125 for i in range(n)]
        root_scalar = merkle.hash_from_byte_slices_scalar(leaves)
        was = merkle.hashhub_active()
        merkle.use_hashhub(True)
        try:
            assert merkle.hash_from_byte_slices(leaves) == root_scalar
            ts, tb = _paired_best(
                lambda: merkle.hash_from_byte_slices_scalar(leaves),
                lambda: merkle.hash_from_byte_slices(leaves),
            )
            row = {
                "scalar_leaves_per_s": round(n / ts, 1),
                "batched_cpu_leaves_per_s": round(n / tb, 1),
                "speedup": round(ts / tb, 2),
            }
            if device_on:
                saved = hash_hub.MIN_DEVICE_BATCH
                hash_hub.MIN_DEVICE_BATCH = 1
                try:
                    assert merkle.hash_from_byte_slices(leaves) == root_scalar
                    _, td = _paired_best(
                        lambda: None, lambda: merkle.hash_from_byte_slices(leaves)
                    )
                    row["device_leaves_per_s"] = round(n / td, 1)
                    row["device_speedup"] = round(ts / td, 2)
                finally:
                    hash_hub.MIN_DEVICE_BATCH = saved
        finally:
            merkle.use_hashhub(was)
        out["leaves"][str(n)] = row
        log(
            f"merkle {n:>6} leaves: scalar {row['scalar_leaves_per_s']:>12,.0f}/s "
            f"batched {row['batched_cpu_leaves_per_s']:>12,.0f}/s "
            f"-> {row['speedup']:.2f}x"
            + (
                f" device {row['device_leaves_per_s']:,.0f}/s"
                if "device_leaves_per_s" in row
                else ""
            )
        )

    # -- block-hash/s: header root with memoization stripped per rep ----
    bid = BlockID(b"\xab" * 32, PartSetHeader(4, b"\xcd" * 32))
    sigs = tuple(
        blk.CommitSig(
            flag=blk.BLOCK_ID_FLAG_COMMIT,
            validator_address=bytes([i % 256]) * 20,
            timestamp_ns=1_700_000_000_000_000_000 + i,
            signature=bytes([i % 256]) * 64,
        )
        for i in range(50)
    )
    commit = blk.Commit(height=2, round=0, block_id=bid, signatures=sigs)
    hdr = blk.Header(
        chain_id="bench",
        height=3,
        time_ns=1_700_000_000_000_000_000,
        last_block_id=bid,
        last_commit_hash=commit.hash(),
        proposer_address=b"\x01" * 20,
        validators_hash=b"\x02" * 32,
        next_validators_hash=b"\x02" * 32,
        app_hash=b"\x03" * 32,
    )
    iters = 2000
    was = merkle.hashhub_active()

    def _hash_headers():
        # replace() yields a fresh frozen instance, dropping the memo —
        # the 14-field tree build is what's measured
        for _ in range(iters):
            _dc_replace(hdr).hash()

    try:
        merkle.use_hashhub(False)
        assert _dc_replace(hdr).hash() == _dc_replace(hdr).hash()
        ref = _dc_replace(hdr).hash()
        merkle.use_hashhub(True)
        assert _dc_replace(hdr).hash() == ref, "builder A/B root mismatch"

        def _scalar():
            merkle.use_hashhub(False)
            _hash_headers()

        def _batched():
            merkle.use_hashhub(True)
            _hash_headers()

        ts, tb = _paired_best(_scalar, _batched, reps=7)
    finally:
        merkle.use_hashhub(was)
    out["block_hash"] = {
        "scalar_per_s": round(iters / ts, 1),
        "batched_per_s": round(iters / tb, 1),
        "speedup": round(ts / tb, 2),
    }
    log(
        f"merkle header-hash: scalar {out['block_hash']['scalar_per_s']:,.0f}/s "
        f"batched {out['block_hash']['batched_per_s']:,.0f}/s "
        f"-> {out['block_hash']['speedup']:.2f}x"
    )

    # -- chaos_soak blocks/s with the tree builder flipped ---------------
    if os.environ.get("TMTPU_BENCH_MERKLE_SOAK") != "0":
        from tendermint_tpu.consensus import scenarios as sc

        seed = int(os.environ.get("TMTPU_BENCH_SOAK_SEED", "7") or 7)
        was = merkle.hashhub_active()
        soak: dict = {"n_vals": soak_vals, "seed": seed, "scenario": "baseline"}
        try:
            for label, enabled in (("scalar", False), ("hashhub", True)):
                merkle.use_hashhub(enabled)

                async def one(_n=soak_vals):
                    return await sc.run_scenario(
                        "baseline",
                        n_vals=_n,
                        target_height=2,
                        seed=seed,
                        timeout_s=300.0,
                        stall_s=90.0,
                        time_scale=4.0,
                        degree=8,
                    )

                t0 = time.perf_counter()
                try:
                    res = asyncio.run(
                        asyncio.wait_for(one(), 360.0)
                    ).as_dict()
                except Exception as e:  # noqa: BLE001 — structured outcome
                    res = {"outcome": f"error: {e!r}"[:200]}
                res["wall_s"] = round(time.perf_counter() - t0, 2)
                soak[label] = res
                log(
                    f"merkle soak[{label}] {res.get('outcome', '?')} "
                    f"{res.get('blocks_per_s', 0)} blk/s "
                    f"wall={res['wall_s']}s"
                )
            bs = soak.get("scalar", {}).get("blocks_per_s") or 0
            bh = soak.get("hashhub", {}).get("blocks_per_s") or 0
            soak["soak_speedup"] = round(bh / bs, 2) if bs else None
        finally:
            merkle.use_hashhub(was)
        out["chaos_soak_ab"] = soak
    out["hashhub_stats"] = hash_hub.stats_snapshot()
    return out


def bench_byz_soak(sizes: tuple = (4, 50)) -> dict:
    """byz_soak config: Byzantine strategies over real routers measured
    per round — blocks/s under each traitor strategy, time-to-evidence-
    commit (heights from the committed pair's equivocation to its
    on-chain commitment), and the cross-node safety auditor's verdict
    (consensus/byzantine.audit_net), at 4 and 50 validators. BOUNDED,
    structured outcomes (the multichip/chaos_soak discipline): the
    scenario engine's liveness watchdog plus an outer asyncio timeout
    mean a wedge or an escape is a record, never a hang. The 50-row
    runs a trimmed strategy list with a height-4 target (evidence needs
    heights of headroom to commit)."""
    import asyncio

    from tendermint_tpu.consensus import scenarios as sc

    seed = int(os.environ.get("TMTPU_BENCH_BYZ_SEED", "7") or 7)
    out: dict = {"seed": seed, "runs": []}
    for n_vals in sizes:
        small = n_vals <= 8
        names = (
            [
                "byz_equivocation",
                "byz_equivocation_partition",
                "byz_amnesia_skew",
                "byz_withhold",
                "byz_invalid_sig",
                "byz_flood_lies",
                "byz_full_taxonomy",
            ]
            if small
            else [
                "byz_equivocation",
                "byz_invalid_sig",
                "byz_full_taxonomy",
            ]
        )
        timeout_s = 90.0 if small else 600.0
        for name in names:
            t0 = time.perf_counter()

            async def one(_name=name, _n=n_vals, _to=timeout_s):
                return await sc.run_scenario(
                    _name,
                    n_vals=_n,
                    target_height=4,
                    seed=seed,
                    timeout_s=_to,
                    stall_s=30.0 if small else 150.0,
                    time_scale=1.0 if small else 6.0,
                    degree=8,
                    audit_k=3 if small else 6,
                )

            try:
                full = asyncio.run(
                    asyncio.wait_for(one(), timeout_s + 60.0)
                ).as_dict()
                audit = full.get("audit") or {}
                ev_heights = audit.get("evidence_commit_heights") or {}
                # time-to-evidence-commit: worst lag across traitors
                # (commit height − the equivocation height the committed
                # pair attributes — the auditor's promptness anchor)
                lags = list((audit.get("evidence_lag_heights") or {}).values())
                tte = max(lags) if lags else None
                res = {
                    "scenario": name,
                    "n_vals": n_vals,
                    "outcome": full["outcome"],
                    "blocks_per_s": full["blocks_per_s"],
                    "elapsed_s": full["elapsed_s"],
                    "byz_indices": full["byz_indices"],
                    "byz_action_counts": [
                        b.get("counts", {}) for b in full["byz_actions"]
                    ],
                    "audit_ok": audit.get("ok"),
                    "evidence_committed": len(ev_heights),
                    "evidence_commit_heights": ev_heights,
                    "time_to_evidence_commit_heights": tte,
                    "conflicting_commits": len(
                        audit.get("conflicting_commits") or []
                    ),
                    "peer_penalties": audit.get("peer_penalties") or {},
                }
            except Exception as e:  # noqa: BLE001 — structured outcome
                res = {
                    "scenario": name,
                    "n_vals": n_vals,
                    "outcome": f"error: {e!r}"[:200],
                }
            res["wall_s"] = round(time.perf_counter() - t0, 2)
            out["runs"].append(res)
            log(
                f"byz_soak {n_vals:>3}v {name:<26} "
                f"{res.get('outcome', '?'):<7} "
                f"audit={'ok' if res.get('audit_ok') else 'FAIL'} "
                f"ev={res.get('evidence_committed', 0)} "
                f"{res.get('blocks_per_s', 0)} blk/s wall={res['wall_s']}s"
            )
    ok = [
        r
        for r in out["runs"]
        if r.get("outcome") == "ok" and r.get("audit_ok")
    ]
    out["ok_runs"] = len(ok)
    out["total_runs"] = len(out["runs"])
    return out


def bench_routernet_xl(rows: tuple = ((50, 2),)) -> dict:
    """routernet_xl config: multi-process committees over real sockets
    (consensus/routernet_xl) measured per round. Each headline row is
    (validators × worker processes) over TCP with the full
    SecretConnection handshake on every cross-slice link, one shared
    verifyd sidecar (all workers pointed at it via TMTPU_VERIFYD_SOCK),
    and a mid-run SIGKILL + respawn of the last worker — so a row
    yields blocks/s, time-to-recover (WAL repair + re-handshake +
    catch-up across a process boundary), and the daemon's cross-tenant
    occupancy. A small-committee transport A/B (TCP vs UDS at 2 workers
    vs in-process memory at 1 worker — memory links cannot cross a
    process) isolates the socket tax. BOUNDED, structured outcomes (the
    chaos_soak discipline): XLNet's aggregated liveness watchdog plus
    an outer asyncio timeout make a wedge, a torn worker, or a timeout
    a record, never a hang. Rows default to 50×2 on CPU;
    TMTPU_BENCH_XL_ROWS (e.g. "50:2,150:4,500:4") widens to the paper's
    150/500-validator scales."""
    import asyncio

    from tendermint_tpu.consensus import routernet_xl as xl
    from tendermint_tpu.consensus.scenarios import Event

    seed = int(os.environ.get("TMTPU_BENCH_XL_SEED", "7") or 7)
    out: dict = {"seed": seed, "rows": [], "transport_ab": []}

    def budget(n_vals: int) -> tuple[float, float, float]:
        """(timeout_s, stall_s, time_scale) by committee size — the
        slow-soak envelopes from tests/test_routernet_xl.py."""
        if n_vals <= 8:
            return 180.0, 60.0, 1.0
        if n_vals <= 64:
            return 420.0, 150.0, 4.0
        if n_vals <= 200:
            return 900.0, 300.0, 8.0
        return 3000.0, 900.0, 15.0

    def one(label: str, **kw) -> dict:
        t0 = time.perf_counter()
        to = kw.get("timeout_s", 300.0)
        try:
            res = asyncio.run(
                asyncio.wait_for(xl.run_xl(**kw), to + 120.0)
            )
            rec = {
                k: res.get(k)
                for k in (
                    "outcome",
                    "scenario",
                    "n_vals",
                    "workers",
                    "transport",
                    "blocks_per_s",
                    "recover_s",
                    "honest_min",
                    "elapsed_s",
                    "process_events_applied",
                    "verifyd",
                    "worker_errors",
                )
            }
            rec["audit_ok"] = bool((res.get("audit") or {}).get("ok"))
        except Exception as e:  # noqa: BLE001 — structured outcome
            rec = {"outcome": f"error: {e!r}"[:200]}
        rec["label"] = label
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        rec_r = rec.get("recover_s")
        log(
            f"routernet_xl {label:<22} {rec.get('outcome', '?'):<7} "
            f"{rec.get('blocks_per_s', 0)} blk/s "
            f"recover={'-' if rec_r is None else f'{rec_r}s'} "
            f"wall={rec['wall_s']}s"
        )
        return rec

    # headline rows: blocks/s + time-to-recover + verifyd occupancy at
    # each (validators × workers) scale, kill+respawn of the last worker
    for n_vals, workers in rows:
        to, stall, scale = budget(n_vals)
        out["rows"].append(
            one(
                f"{n_vals}v x{workers}w tcp",
                scenario="baseline",
                n_vals=n_vals,
                workers=workers,
                transport="tcp",
                seed=seed,
                target_height=2,
                preload=4,
                timeout_s=to,
                stall_s=stall,
                time_scale=scale,
                use_verifyd=True,
                durable=True,
                # 1-core boxes need slower, bigger-batch gossip at
                # committee scale (see the 500-val soak test)
                gossip_sleep=1.0 if n_vals > 200 else None,
                process_events=(
                    Event(2.0, "kill_worker", node=workers - 1),
                    Event(4.0, "restart_worker", node=workers - 1),
                ),
            )
        )
    # transport A/B at a small committee: the socket tax isolated from
    # committee-scale costs. memory runs 1 worker — in-process links
    # only — and is the A/B's no-socket control.
    ab_vals = int(os.environ.get("TMTPU_BENCH_XL_AB_VALS", "4"))
    to, stall, scale = budget(ab_vals)
    for transport, workers in (("tcp", 2), ("unix", 2), ("memory", 1)):
        out["transport_ab"].append(
            one(
                f"{ab_vals}v x{workers}w {transport}",
                scenario="baseline",
                n_vals=ab_vals,
                workers=workers,
                transport=transport,
                seed=seed,
                target_height=3,
                preload=4,
                timeout_s=to,
                stall_s=stall,
                time_scale=scale,
                durable=False,
            )
        )
    ok = [
        r
        for r in out["rows"] + out["transport_ab"]
        if r.get("outcome") == "ok"
    ]
    out["ok_runs"] = len(ok)
    out["total_runs"] = len(out["rows"]) + len(out["transport_ab"])
    return out


def bench_verify_hub(
    n_vals: int, n_submitters: int = 8, per_submitter: int = 200
) -> dict:
    """VerifyHub config: N concurrent submitters each feeding
    SINGLE-vote requests through the sync facade — the live-consensus
    shape (one vote at a time per caller, concurrency only across
    callers). Reports coalesced sigs/sec, mean batch occupancy, and the
    sequential single-vote CPU baseline the hub must beat. Duplicate
    submissions (the same vote from 'many peers') exercise the dedup
    cache; throughput is computed over UNIQUE verifications to keep the
    headline honest."""
    import queue as _queue
    import threading as _threading

    from tendermint_tpu import testing as tt
    from tendermint_tpu.crypto.verify_hub import VerifyHub
    from tendermint_tpu.types.keys import SignedMsgType

    chain_id = "hub-bench"
    vals, keys = tt.make_validator_set(min(n_vals, 64), power=10)
    key_list = [keys[v.address] for v in vals.validators]
    n_unique = n_submitters * per_submitter
    items = []
    for i in range(n_unique):
        vi = i % len(key_list)
        bid = tt.make_block_id(b"hub-%d" % (i // len(key_list)))
        vote = tt.make_vote(
            chain_id, key_list[vi], vi, 1 + i // len(key_list), 0,
            SignedMsgType.PREVOTE, bid,
        )
        items.append(
            (vals.validators[vi].pub_key, vote.sign_bytes(chain_id), vote.signature)
        )

    # sequential single-vote CPU baseline: one verify_signature at a
    # time, the pre-hub live-consensus path
    base_n = min(len(items), 400)
    t0 = time.perf_counter()
    for pk, msg, sig in items[:base_n]:
        assert pk.verify_signature(msg, sig)
    seq_rate = base_n / (time.perf_counter() - t0)
    log(f"hub bench: sequential single-vote baseline {seq_rate:,.1f} sigs/s")

    hub = VerifyHub(max_batch=256, window_ms=2.0, cache_size=4 * n_unique)
    hub.start()
    try:
        work: _queue.SimpleQueue = _queue.SimpleQueue()
        for it in items:
            work.put(it)
        # a 10% sample re-enters the queue — gossip duplicates for the
        # cache-hit measurement
        for d in items[::10]:
            work.put(d)
        errors: list = []

        def submitter():
            while True:
                try:
                    pk, msg, sig = work.get_nowait()
                except _queue.Empty:
                    return
                try:
                    if not hub.verify_sync(pk, msg, sig):
                        errors.append("bad verdict")
                except Exception as e:  # noqa: BLE001
                    errors.append(repr(e))

        threads = [
            _threading.Thread(target=submitter, name=f"hub-sub-{i}")
            for i in range(n_submitters)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert not errors, errors[:3]
        s = hub.stats()
        hub_rate = n_unique / dt
        out = {
            "hub_sigs_per_s": round(hub_rate, 1),
            "sequential_cpu_sigs_per_s": round(seq_rate, 1),
            "speedup_vs_sequential": round(hub_rate / seq_rate, 2),
            "mean_batch_occupancy": round(s["mean_occupancy"], 2),
            "dispatches": int(s["dispatches"]),
            "cache_hits": int(s["cache_hits"] + s["coalesced"]),
            "submitters": n_submitters,
        }
        log(
            f"hub bench: {n_unique} unique sigs via {n_submitters} submitters in "
            f"{dt:.2f}s -> {hub_rate:,.1f} sigs/s (occupancy "
            f"{out['mean_batch_occupancy']}, {out['dispatches']} dispatches, "
            f"{out['cache_hits']} cache/coalesce hits)"
        )
        return out
    finally:
        hub.stop()


async def _bench_consensus_ingest_async(
    n_vals: int, waves: int, n_peers: int
) -> dict:
    """consensus_ingest config: votes ingested+applied per second by ONE
    node fed concurrently by `n_peers` simulated gossip peers — the
    single-node occupancy story. Baseline: the sequential facade
    (ingest_pipeline off → per-vote sync hub verify, occupancy pinned at
    1). Pipelined: stage-1 async verify with in-order apply. Each wave
    is a fresh set of uniquely-signed votes (rounds 0-1, both types,
    tallies kept below 2/3 so the parked state machine never
    transitions); the vote-set is reset between waves so the dedup
    stage sees every wave cold."""
    import asyncio

    from tendermint_tpu.consensus.harness import Node, fast_config, make_genesis
    from tendermint_tpu.consensus.types import HeightVoteSet
    from tendermint_tpu.crypto import verify_hub as vh
    from tendermint_tpu.types.block import NIL_BLOCK_ID
    from tendermint_tpu.types.keys import SignedMsgType
    from tendermint_tpu.types.vote import Vote

    genesis, keys = make_genesis(n_vals)
    # keep every (round, type) tally safely below 2/3 of total power
    cap = max(1, (2 * n_vals) // 3 - 2)
    combos = (
        (0, SignedMsgType.PREVOTE),
        (0, SignedMsgType.PRECOMMIT),
        (1, SignedMsgType.PREVOTE),
        (1, SignedMsgType.PRECOMMIT),
    )

    async def run_mode(pipeline: bool, n_waves: int) -> dict:
        cfg = fast_config()
        cfg.ingest_pipeline = pipeline
        # deep enough that a whole gossip wave overlaps: thread-handoff
        # latency amortizes across the wave instead of per vote
        cfg.ingest_max_inflight = 256
        # park the observer SM: tally votes, never drive rounds
        cfg.timeout_propose_ns = 3_600 * 10**9
        cfg.timeout_commit_ns = 0
        node = Node(genesis, None, config=cfg)
        await node.start()
        cs = node.cs
        vals = cs.rs.validators
        chain_id = cs.state.chain_id
        idx_key = sorted(
            (vals.get_by_address(k.pub_key().address())[0], k) for k in keys
        )
        base_ts = 1_700_000_000_000_000_000
        log(
            f"ingest bench[{'pipelined' if pipeline else 'sequential'}]: "
            f"signing {n_waves}x{len(combos) * cap} votes …"
        )
        wave_votes = []
        for w in range(n_waves):
            votes = []
            for round_, type_ in combos:
                for idx, key in idx_key[:cap]:
                    v = Vote(
                        type=type_,
                        height=cs.rs.height,
                        round=round_,
                        block_id=NIL_BLOCK_ID,
                        timestamp_ns=base_ts + w,  # unique sign-bytes per wave
                        validator_address=key.pub_key().address(),
                        validator_index=idx,
                        signature=b"",
                    )
                    sig = key.sign(v.sign_bytes(chain_id))
                    votes.append(
                        Vote(**{**v.__dict__, "signature": sig})
                    )
            wave_votes.append(votes)

        def tallied() -> int:
            total = 0
            for round_, type_ in combos:
                vs = (
                    cs.rs.votes.prevotes(round_)
                    if type_ == SignedMsgType.PREVOTE
                    else cs.rs.votes.precommits(round_)
                )
                if vs is not None:
                    total += sum(1 for v in vs.votes if v is not None)
            return total

        async def peer_feed(votes):
            for v in votes:
                await cs.add_vote(v, "bench-peer")

        total = 0
        t0 = time.perf_counter()
        try:
            for votes in wave_votes:
                tasks = [
                    asyncio.get_running_loop().create_task(
                        peer_feed(votes[p::n_peers])
                    )
                    for p in range(n_peers)
                ]
                await asyncio.gather(*tasks)
                want = len(votes)
                while tallied() < want:
                    await asyncio.sleep(0.002)
                total += want
                # fresh tally for the next wave (dedup stage sees it cold)
                cs.rs.votes = HeightVoteSet(chain_id, cs.rs.height, vals)
            dt = time.perf_counter() - t0
        finally:
            ingest_stats = dict(cs.ingest.stats) if cs.ingest else {}
            await node.stop()
        return {"rate": total / dt, "votes": total, "dt": dt, "ingest": ingest_stats}

    out: dict = {}
    # sequential facade baseline (~4ms/vote on the pure-python verify
    # fallback: fewer waves keep the baseline from eating the budget)
    hub = vh.acquire_hub(max_batch=256, window_ms=2.0, cache_size=8192)
    try:
        seq = await run_mode(False, max(1, waves // 3))
        s = hub.stats()
        out["sequential_votes_per_s"] = round(seq["rate"], 1)
        out["sequential_occupancy"] = round(s["mean_occupancy"], 2)
    finally:
        vh.release_hub()

    hub = vh.acquire_hub(max_batch=256, window_ms=2.0, cache_size=8192)
    try:
        # light concurrent backfill (pre-signed, one key) so the lane
        # mix under live load is measured, not assumed
        import threading as _threading

        bf_priv = keys[0]
        bf_pub = bf_priv.pub_key()
        bf_items = [
            (bf_pub, b"ingest-backfill-%d" % i, bf_priv.sign(b"ingest-backfill-%d" % i))
            for i in range(128)
        ]

        def backfill_feed():
            try:
                hub.verify_many(bf_items, lane="backfill")
            except Exception as e:  # noqa: BLE001
                log(f"backfill feeder failed: {e!r}")

        feeder = _threading.Thread(target=backfill_feed)
        feeder.start()
        pipe = await run_mode(True, waves)
        feeder.join()
        s = hub.stats()
        out.update(
            pipelined_votes_per_s=round(pipe["rate"], 1),
            speedup_vs_sequential=round(pipe["rate"] / seq["rate"], 2),
            mean_batch_occupancy=round(s["mean_occupancy"], 2),
            lane_live_sigs=int(s["lane_live_dispatched"]),
            lane_backfill_sigs=int(s["lane_backfill_dispatched"]),
            lane_promotions=int(s["lane_promotions"]),
            ingest_pre_verified=int(pipe["ingest"].get("pre_verified", 0)),
            ingest_dedup_drops=int(pipe["ingest"].get("dedup_drops", 0)),
            peers=n_peers,
        )
    finally:
        vh.release_hub()
    log(
        f"consensus ingest: pipelined {out['pipelined_votes_per_s']:,.1f} votes/s "
        f"(occupancy {out['mean_batch_occupancy']}, lane mix "
        f"{out['lane_live_sigs']}/{out['lane_backfill_sigs']} live/backfill) vs "
        f"sequential {out['sequential_votes_per_s']:,.1f} votes/s -> "
        f"{out['speedup_vs_sequential']}x"
    )
    return out


def bench_consensus_ingest(n_vals: int = 64, waves: int = 6, n_peers: int = 8) -> dict:
    import asyncio

    return asyncio.run(_bench_consensus_ingest_async(n_vals, waves, n_peers))


async def _bench_tx_flood_async(n_clients: int, txs_per_client: int) -> dict:
    """tx_flood config: open-loop flood of signed-envelope txs from
    `n_clients` distinct senders through the TxIngress front door —
    sustained admitted tx/s, per-tx p99 admission (CheckTx) latency and
    the shed rate under explicit backpressure. Clients are OPEN loop:
    they submit without waiting for verdicts (bursts with a cooperative
    yield), so when the bounded intake fills the ingress must shed with
    busy, never buffer; the pipeline keeps draining behind the flood and
    the number that matters is what it sustains, not what it drops."""
    import asyncio

    from tendermint_tpu.abci import types as abci_types
    from tendermint_tpu.abci.application import BaseApplication
    from tendermint_tpu.abci.client import LocalClient
    from tendermint_tpu.config import MempoolConfig
    from tendermint_tpu.crypto import verify_hub as vh
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.mempool.ingress import TxIngress, make_signed_tx
    from tendermint_tpu.mempool.pool import PriorityMempool

    class FloodApp(BaseApplication):
        def check_tx(self, req):
            # pseudo-random priority per tx (last byte = signature
            # tail): cheap variety in the resident ordering without an
            # app-state lookup; the pool is sized to hold the whole
            # flood, so eviction dynamics are tested in the suite, not
            # measured here
            prio = req.tx[-1] if req.tx else 0
            return abci_types.ResponseCheckTx(priority=prio, gas_wanted=1)

    n_total = n_clients * txs_per_client
    log(f"tx flood: signing {n_total} envelope txs from {n_clients} clients …")
    t0 = time.perf_counter()
    keys = [Ed25519PrivKey.generate() for _ in range(n_clients)]
    txs: list[bytes] = []
    for ci, key in enumerate(keys):
        for nonce in range(txs_per_client):
            txs.append(
                make_signed_tx(key, nonce, b"flood-%d-%d" % (ci, nonce))
            )
    sign_dt = time.perf_counter() - t0
    log(f"signed {n_total} txs in {sign_dt:.1f}s")

    cfg = MempoolConfig(
        # the pool must not be the bottleneck: this config measures the
        # front door (intake/verify/nonce-lane/checktx), not eviction
        size=n_total + 16,
        max_txs_bytes=1 << 30,
        cache_size=2 * n_total + 16,
    )
    # short park timeout: a shed nonce-0 makes its successor park, and
    # the flood should measure drain speed, not 3s park clocks
    cfg.ingress.nonce_park_timeout_ms = 250.0
    # deep stage-A: concurrent verify awaits are what fill the hub's
    # micro-batches (occupancy ~= workers under saturation)
    cfg.ingress.verify_workers = 64
    pool = PriorityMempool(cfg, LocalClient(FloodApp()))
    ingress = TxIngress(cfg.ingress, pool)
    await ingress.start()

    latencies: list[float] = []

    def on_done(fut, t_sub):
        if fut.exception() is None:
            latencies.append(time.perf_counter() - t_sub)

    t0 = time.perf_counter()
    burst = 256
    for i in range(0, len(txs), burst):
        for tx in txs[i : i + burst]:
            t_sub = time.perf_counter()
            fut = ingress.submit_nowait(tx, source="client")
            fut.add_done_callback(lambda f, t=t_sub: on_done(f, t))
        # open loop: yield so the pipeline runs, but never wait for it
        await asyncio.sleep(0)
    # drain: wait (bounded) for the pipeline + parked successors
    deadline = time.perf_counter() + 120.0
    while (
        ingress.occupancy > 0 or ingress.parked_count() > 0
    ) and time.perf_counter() < deadline:
        await asyncio.sleep(0.01)
    dt = time.perf_counter() - t0
    stats = dict(ingress.stats)
    admitted = int(pool.stats["admitted"])
    shed = int(stats["shed"])
    await ingress.stop()

    latencies.sort()
    p = lambda q: latencies[min(len(latencies) - 1, int(q * len(latencies)))] if latencies else 0.0  # noqa: E731
    out = {
        "clients": n_clients,
        "txs_per_client": txs_per_client,
        "submitted_total": n_total,
        "admitted": admitted,
        "admitted_tx_per_s": round(admitted / dt, 1),
        "checktx_p50_ms": round(p(0.50) * 1e3, 3),
        "checktx_p99_ms": round(p(0.99) * 1e3, 3),
        "shed": shed,
        "shed_rate": round(shed / n_total, 4),
        "parked": int(stats["parked"]),
        "park_expired": int(stats["park_expired"]),
        "park_adopted": int(stats["park_adopted"]),
        "sig_failed": int(stats["sig_failed"]),
        "flood_dt_s": round(dt, 3),
        "sign_dt_s": round(sign_dt, 1),
    }
    hub = vh.running_hub()
    if hub is not None:
        s = hub.stats()
        out["hub_occupancy"] = round(s["mean_occupancy"], 2)
        out["hub_backfill_sigs"] = int(s["lane_backfill_dispatched"])
    log(
        f"tx flood: {out['admitted_tx_per_s']:,.1f} admitted tx/s "
        f"(p99 {out['checktx_p99_ms']}ms, shed {out['shed_rate']:.1%}, "
        f"{admitted}/{n_total} admitted)"
    )
    return out


async def _bench_tx_flood_with_hub(n_clients: int, txs_per_client: int) -> dict:
    from tendermint_tpu.crypto import verify_hub as vh

    # the hub IS the front door's verify engine: envelope signatures
    # micro-batch on its backfill lane, so the flood must run against a
    # live hub to measure the production path (acquired on this loop)
    vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=65536)
    try:
        return await _bench_tx_flood_async(n_clients, txs_per_client)
    finally:
        vh.release_hub()


def bench_tx_flood(n_clients: int = 10_000, txs_per_client: int = 2) -> dict:
    import asyncio

    return asyncio.run(_bench_tx_flood_with_hub(n_clients, txs_per_client))


def bench_commit_ab(n_vals: int = 150, n_commits: int = 2) -> dict:
    """Aggregate-signature A/B (ISSUE 9 / arXiv:2302.00418): the SAME
    chain shape — n_vals validators, n_commits full commits — measured
    under both commit wire schemes:

      eddsa_batch    — one ed25519 signature per validator, batch
                       verified through the existing funnel;
      bls_aggregate  — ONE 96-byte G2 aggregate per commit, pairing
                       verified (BLS aggregation collapses gossip/
                       storage bandwidth to O(1) signatures at the cost
                       of pairing-heavy verification).

    Records, per scheme: commit wire bytes, commit-verify sigs/s (the
    live-consensus per-commit shape), and catch-up blocks/s (the
    blocksync verify_commit_range shape). Verification memos (the
    hash-to-curve LRU that signing pre-populated, the pure-ed25519
    verdict memo) are cleared before every timed pass, so the numbers
    are cold-verify rates, not cache reads. With TMTPU_BLS_TPU=1 and a
    live backend the aggregate check routes through the batched pairing
    kernel; otherwise the load-bearing pure-Python path is what is
    being measured (recorded in `route`)."""
    from tendermint_tpu import testing
    from tendermint_tpu.crypto import bls_math
    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.types import validation
    from tendermint_tpu.types.block import aggregate_commit

    chain_id = "ab-chain"
    out: dict = {"n_vals": n_vals, "n_commits": n_commits}
    for scheme, key_types in (
        ("eddsa_batch", ("ed25519",)),
        ("bls_aggregate", ("bls12381",)),
    ):
        log(f"commit_ab: building {n_vals}-val {scheme} commits …")
        vals, by_addr = testing.make_validator_set(
            n_vals, key_types=key_types, seed=b"ab-" + scheme.encode()
        )
        commits = []
        for h in range(1, n_commits + 1):
            bid = testing.make_block_id(b"ab%d" % h)
            c = testing.make_commit(
                chain_id, h, 0, bid, vals, by_addr,
                timestamp_ns=1_700_000_000_000_000_000 + h,
            )
            if scheme == "bls_aggregate":
                c = aggregate_commit(c, vals)
            commits.append((vals, bid, h, c))
        wire = len(commits[0][3].encode())
        bls_math._H2_MEMO.clear()
        _ed._VERIFY_MEMO.clear()
        t0 = time.perf_counter()
        for vs, bid, h, c in commits:
            validation.verify_commit(chain_id, vs, bid, h, c)
        dt = time.perf_counter() - t0
        bls_math._H2_MEMO.clear()
        _ed._VERIFY_MEMO.clear()
        t0 = time.perf_counter()
        validation.verify_commit_range(chain_id, commits)
        dt_range = time.perf_counter() - t0
        out[scheme] = {
            "commit_wire_bytes": wire,
            "sig_bytes_per_commit": 96 if scheme == "bls_aggregate" else 64 * n_vals,
            "verify_sigs_per_s": round(n_vals * n_commits / dt, 1),
            "verify_ms_per_commit": round(dt / n_commits * 1e3, 2),
            "catchup_blocks_per_s": round(n_commits / dt_range, 3),
        }
        log(
            f"commit_ab[{scheme}]: {wire} B/commit, "
            f"{out[scheme]['verify_sigs_per_s']:,.0f} sigs/s, "
            f"{out[scheme]['catchup_blocks_per_s']} catch-up blocks/s"
        )
    out["wire_ratio"] = round(
        out["eddsa_batch"]["commit_wire_bytes"]
        / out["bls_aggregate"]["commit_wire_bytes"],
        2,
    )
    out["route"] = (
        "pairing-kernel" if os.environ.get("TMTPU_BLS_TPU") == "1" else "pure-python"
    )
    return out


def bench_light_fleet(
    n_vals: int = 150,
    n_clients: int = 64,
    n_heights: int = 6,
    timeout_s: float = 420.0,
) -> dict:
    """light_fleet config: N open-loop light clients syncing genesis→tip
    against ONE LightD (light/fleet.py) — the first genuinely read-heavy
    "millions of users" workload. Measured per hop-proof scheme
    (aggregate-hop vs per-sig, the arXiv:2302.00418 A/B):

      syncs/s, p50/p99 sync latency, hop-cache hit rate, shed rate
      (bounded sessions + explicit busy-shed), verify sigs/s
      (signatures COVERED per second — one aggregate pairing covers the
      whole committee), hop-proof wire bytes, and the hop-cache
      amortization factor: (cold per-client verification hops × N) /
      hops LightD actually verified.

    BOUNDED (the multichip/chaos_soak discipline): every phase runs
    under an outer asyncio timeout and returns a structured outcome on
    wedge/error — never a hang. CPU-image scale-down via
    TMTPU_BENCH_LF_VALS / _CLIENTS / _HEIGHTS (pure-python BLS signing
    dominates chain construction there; the wire and amortization
    numbers are backend-independent)."""
    import asyncio

    from tendermint_tpu import testing
    from tendermint_tpu.config import LightDConfig
    from tendermint_tpu.light import fleet as lf
    from tendermint_tpu.light.client import LightClient, TrustOptions

    chain_id = "lf-chain"
    out: dict = {
        "n_vals": n_vals,
        "n_clients": n_clients,
        "n_heights": n_heights,
        "schemes": {},
    }

    async def _one_scheme(scheme: str, chain, aggregate_hops: bool) -> dict:
        import tempfile

        from tendermint_tpu.libs.watchdog import LoopWatchdog

        # watchdog + outer timeout (the chaos_soak bounding discipline):
        # the wait_for below hard-bounds the phase; the loop watchdog
        # dumps a stack + flight-recorder report if the serving loop
        # wedges mid-phase, so a hang is diagnosable from disk
        wd = LoopWatchdog(
            tempfile.mkdtemp(prefix="light-fleet-wd-"), threshold_s=30.0
        )
        wd.start()
        trust = TrustOptions(
            period_ns=10**18, height=1, hash=chain[0].header.hash()
        )
        now = chain[-1].header.time_ns + 10**9
        # cold baseline: ONE client verifying alone — the per-client
        # work the fleet would multiply by N without a serving layer
        cold_prov = testing.make_list_provider(chain, chain_id)
        lc = LightClient(chain_id, trust, cold_prov)
        t0 = time.perf_counter()
        await lc.verify_light_block_at_height(n_heights, now)
        cold_s = time.perf_counter() - t0
        cold_hops = cold_prov.fetches  # anchor + every hop fetched

        prov = testing.make_list_provider(chain, chain_id)
        d = lf.LightD(
            chain_id,
            trust,
            prov,
            config=LightDConfig(
                max_sessions=32, aggregate_hops=aggregate_hops
            ),
        )
        await d.start()
        latencies: list[float] = []
        shed = 0

        async def one_client():
            nonlocal shed
            c0 = time.perf_counter()
            try:
                await d.sync(n_heights, now_ns=now)
            except lf.LightDBusyError:
                shed += 1
                return
            latencies.append(time.perf_counter() - c0)

        try:
            t0 = time.perf_counter()
            await asyncio.gather(*(one_client() for _ in range(n_clients)))
            elapsed = max(time.perf_counter() - t0, 1e-9)
            proof = await d.hop_proof(n_heights)
            stats = dict(d.stats)
        finally:
            await d.stop()
            wd.stop()
        latencies.sort()

        def pct(p: float) -> float:
            if not latencies:
                return 0.0
            return latencies[min(len(latencies) - 1, int(p * len(latencies)))]

        hops = max(stats["hops_verified"], 1.0)
        lookups = stats["hop_cache_hits"] + stats["hop_cache_misses"]
        return {
            "proof_scheme": proof.scheme,
            "hop_proof_wire_bytes": proof.wire_bytes(),
            "sig_bytes_per_hop": (
                96 if proof.scheme == lf.SCHEME_AGGREGATE else 64 * n_vals
            ),
            "syncs_per_s": round(len(latencies) / elapsed, 1),
            "completed": len(latencies),
            "shed": shed,
            "shed_rate": round(shed / n_clients, 4),
            "p50_sync_s": round(pct(0.50), 5),
            "p99_sync_s": round(pct(0.99), 5),
            "hop_cache_hit_rate": round(
                stats["hop_cache_hits"] / lookups if lookups else 0.0, 4
            ),
            "coalesced": stats["coalesced"],
            "hops_verified": stats["hops_verified"],
            "sigs_covered_per_s": round(hops * n_vals / elapsed, 1),
            "cold_client_s": round(cold_s, 4),
            "cold_client_hops": cold_hops,
            # the headline: verification work a cold fleet would have
            # done / work the serving layer actually did
            "amortization_factor": round(
                (cold_hops * n_clients) / max(prov.fetches, 1), 2
            ),
        }

    for scheme, key_types, agg in (
        ("per_sig", ("ed25519",), False),
        ("bls_aggregate", ("bls12381",), True),
    ):
        t0 = time.perf_counter()
        try:
            log(f"light_fleet: building {n_vals}-val {scheme} chain …")
            vals, by_addr = testing.make_validator_set(
                n_vals, key_types=key_types, seed=b"lf-" + scheme.encode()
            )
            chain = testing.make_light_chain(
                n_heights, vals, by_addr, chain_id
            )
            build_s = time.perf_counter() - t0

            async def bounded(_chain=chain, _scheme=scheme, _agg=agg):
                return await asyncio.wait_for(
                    _one_scheme(_scheme, _chain, _agg), timeout_s
                )

            rec = asyncio.run(bounded())
            rec["outcome"] = "ok"
            rec["chain_build_s"] = round(build_s, 2)
        except Exception as e:  # noqa: BLE001 — structured outcome
            rec = {"outcome": f"error: {e!r}"[:200]}
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        out["schemes"][scheme] = rec
        log(
            f"light_fleet[{scheme}]: {rec.get('outcome')} "
            f"{rec.get('syncs_per_s', 0)} syncs/s "
            f"{rec.get('sigs_covered_per_s', 0)} sigs/s "
            f"hit={rec.get('hop_cache_hit_rate', 0)} "
            f"shed={rec.get('shed_rate', 0)} "
            f"amortization={rec.get('amortization_factor', 0)}x "
            f"wire={rec.get('hop_proof_wire_bytes', 0)}B"
        )
    per, agg = out["schemes"].get("per_sig", {}), out["schemes"].get(
        "bls_aggregate", {}
    )
    if per.get("outcome") == "ok" and agg.get("outcome") == "ok":
        out["wire_ratio"] = round(
            per["hop_proof_wire_bytes"] / agg["hop_proof_wire_bytes"], 2
        )
        out["sig_bytes_ratio"] = round(
            per["sig_bytes_per_hop"] / agg["sig_bytes_per_hop"], 1
        )
    return out


def bench_statesync_fleet(
    n_blocks: int = 64,
    n_vals: int = 21,
    n_joiners: int = 8,
    ab_vals: int = 64,
    ab_heights: int = 32,
    timeout_s: float = 420.0,
) -> dict:
    """statesync config: the BootFleet mass-onboarding workload — two
    bounded phases, both structured-outcome (the chaos_soak discipline):

      join_wave    — N concurrent cold joiners statesync against ONE
                     donor's BootD over the real reactor protocol:
                     joiners/s, chunks/s, time-to-synced p50/p99, the
                     donor-overhead story (app store reads per joiner +
                     the shared-chunk-cache amortization factor), shed
                     count at the session bound.
      backfill_ab  — the hub backfill-lane verification A/B on the same
                     window shape: per-sig ed25519 commits mega-batched
                     through verify_commit_range vs a BLS committee's
                     aggregate commits (ONE pairing per height via
                     verify_hub.verify_aggregate). Verification memos
                     cleared first, so both are cold-verify rates.

    CPU-image scale-down via TMTPU_BENCH_SS_* (pure-python BLS signing
    dominates A/B chain construction there; the amortization and wire
    numbers are backend-independent)."""
    import asyncio
    import tempfile

    from tendermint_tpu import testing
    from tendermint_tpu.libs.watchdog import LoopWatchdog
    from tendermint_tpu.statesync.fleet import verify_backfill_batch

    out: dict = {
        "n_blocks": n_blocks,
        "n_vals": n_vals,
        "n_joiners": n_joiners,
        "join_wave": {},
        "backfill_ab": {"n_vals": ab_vals, "n_heights": ab_heights},
    }

    # -- phase 1: the join wave -----------------------------------------
    t0 = time.perf_counter()
    try:
        wd = LoopWatchdog(
            tempfile.mkdtemp(prefix="statesync-wd-"), threshold_s=30.0
        )

        async def wave() -> dict:
            wd.start()
            try:
                return await asyncio.wait_for(
                    testing.statesync_fleet_scenario(
                        n_blocks, n_vals, n_joiners
                    ),
                    timeout_s,
                )
            finally:
                wd.stop()

        res = asyncio.run(wave())
        lat = sorted(res["time_to_synced_s"])

        def pct(p: float) -> float:
            if not lat:
                return 0.0
            return lat[min(len(lat) - 1, int(p * len(lat)))]

        st = res["server_stats"]
        elapsed = max(res["elapsed_s"], 1e-9)
        rec = {
            "outcome": "ok" if res["joined"] == n_joiners else "partial",
            "joined": res["joined"],
            "join_errors": res["join_errors"][:4],
            "joiners_per_s": round(res["joined"] / elapsed, 2),
            "chunks_per_s": round(st["chunks_served"] / elapsed, 1),
            "p50_time_to_synced_s": round(pct(0.50), 4),
            "p99_time_to_synced_s": round(pct(0.99), 4),
            "sheds": st["sheds"],
            "cache_hit_rate": round(
                st["cache_hits"]
                / max(st["cache_hits"] + st["cache_misses"], 1),
                4,
            ),
            # donor overhead: what serving the whole wave actually cost
            # the donor's app — reads amortized by the shared cache
            "donor_store_reads": st["store_reads"],
            "donor_store_reads_per_joiner": round(
                st["store_reads"] / max(res["joined"], 1), 3
            ),
            "chunk_amortization_factor": round(
                st["chunks_served"] / max(st["store_reads"], 1), 2
            ),
            "backfill_sigs": res["joiner_backfill"]["backfill_sigs"],
            "backfill_sigs_per_s": round(
                res["joiner_backfill"]["backfill_sigs"] / elapsed, 1
            ),
            "backfill_batches": res["joiner_backfill"]["backfill_batches"],
        }
    except Exception as e:  # noqa: BLE001 — structured outcome
        rec = {"outcome": f"error: {e!r}"[:200]}
    rec["wall_s"] = round(time.perf_counter() - t0, 2)
    out["join_wave"] = rec
    log(
        f"statesync[join_wave]: {rec.get('outcome')} "
        f"{rec.get('joiners_per_s', 0)} joiners/s "
        f"{rec.get('chunks_per_s', 0)} chunks/s "
        f"p99={rec.get('p99_time_to_synced_s', 0)}s "
        f"amortization={rec.get('chunk_amortization_factor', 0)}x"
    )

    # -- phase 2: backfill verification A/B -----------------------------
    from tendermint_tpu.crypto import bls_math
    from tendermint_tpu.crypto import ed25519 as _ed
    from tendermint_tpu.light.types import LightBlock, SignedHeader
    from tendermint_tpu.types.block import aggregate_commit

    chain_id = "ssab-chain"
    for scheme, key_types, agg in (
        ("per_sig", ("ed25519",), False),
        ("bls_aggregate", ("bls12381",), True),
    ):
        t0 = time.perf_counter()
        try:
            log(f"statesync: building {ab_vals}-val {scheme} backfill window …")
            vals, by_addr = testing.make_validator_set(
                ab_vals, key_types=key_types, seed=b"ssab-" + scheme.encode()
            )
            window = testing.make_light_chain(
                ab_heights, vals, by_addr, chain_id
            )
            if agg:
                window = [
                    LightBlock(
                        SignedHeader(
                            lb.signed_header.header,
                            aggregate_commit(lb.signed_header.commit, vals),
                        ),
                        vals,
                    )
                    for lb in window
                ]
            wire = len(window[0].signed_header.commit.encode())
            bls_math._H2_MEMO.clear()
            _ed._VERIFY_MEMO.clear()

            async def bounded(_w=window):
                return await asyncio.wait_for(
                    verify_backfill_batch(chain_id, _w), timeout_s
                )

            v0 = time.perf_counter()
            n_sigs = asyncio.run(bounded())
            dt = max(time.perf_counter() - v0, 1e-9)
            rec = {
                "outcome": "ok",
                "commit_wire_bytes": wire,
                "heights_per_s": round(ab_heights / dt, 1),
                "verify_sigs": n_sigs,
                # signatures COVERED per second: an aggregate commit
                # covers the committee with one pairing
                "sigs_covered_per_s": round(ab_heights * ab_vals / dt, 1),
            }
        except Exception as e:  # noqa: BLE001 — structured outcome
            rec = {"outcome": f"error: {e!r}"[:200]}
        rec["wall_s"] = round(time.perf_counter() - t0, 2)
        out["backfill_ab"][scheme] = rec
        log(
            f"statesync[backfill:{scheme}]: {rec.get('outcome')} "
            f"{rec.get('heights_per_s', 0)} heights/s "
            f"{rec.get('sigs_covered_per_s', 0)} sigs-covered/s "
            f"wire={rec.get('commit_wire_bytes', 0)}B"
        )
    per = out["backfill_ab"].get("per_sig", {})
    agg_rec = out["backfill_ab"].get("bls_aggregate", {})
    if per.get("outcome") == "ok" and agg_rec.get("outcome") == "ok":
        out["backfill_ab"]["wire_ratio"] = round(
            per["commit_wire_bytes"] / agg_rec["commit_wire_bytes"], 2
        )
    return out


def _multichip_measure(n_sigs: int, reps: int = 2) -> dict:
    """multichip config: sharded vs single-device verification of the
    same batch on the mesh this process holds. Returns sigs/s for both
    routes plus per-device shard occupancy from the dispatch telemetry.
    One device: "not measured" — a virtual CPU mesh says nothing about
    chips (the functional dry run of that path is
    __graft_entry__.dryrun_multichip)."""
    import numpy as np

    import jax

    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.tpu import verify as tpuv

    n_dev = len(jax.devices())
    out: dict = {"n_devices": n_dev, "n_sigs": n_sigs}
    if n_dev < 2:
        out["outcome"] = "not measured: single-device mesh, nothing to shard"
        return out

    items = []
    for i in range(n_sigs):
        priv = Ed25519PrivKey((i + 1).to_bytes(4, "little") * 8)
        msg = b"multichip-%d" % i
        items.append((priv.pub_key().bytes(), msg, priv.sign(msg)))

    def timed(env_on: dict, env_off: list) -> tuple[float, float]:
        for k in env_off:
            os.environ.pop(k, None)
        os.environ.update(env_on)
        try:
            t0 = time.perf_counter()
            bm = tpuv.verify_batch_eq(items)
            warm_s = time.perf_counter() - t0
            assert bool(np.asarray(bm).all()), "multichip batch rejected"
            t0 = time.perf_counter()
            for _ in range(reps):
                bm = tpuv.verify_batch_eq(items)
            return (time.perf_counter() - t0) / reps, warm_s
        finally:
            for k in env_on:
                os.environ.pop(k, None)

    single_dt, single_warm = timed({"TMTPU_NO_SHARDED": "1"}, ["TMTPU_FORCE_SHARDED"])
    bt.SHARD_SIGS.clear()
    shard_dt, shard_warm = timed({"TMTPU_FORCE_SHARDED": "1"}, ["TMTPU_NO_SHARDED"])
    info = tpuv.last_dispatch_info() or {}
    # shard capacity: every chunk pads to one shared bucket, split evenly
    chunk = min(n_sigs, tpuv._MAX_BUCKET)
    n_chunks = (n_sigs + tpuv._MAX_BUCKET - 1) // tpuv._MAX_BUCKET
    bucket = tpuv._bucket(chunk, n_dev)
    cap_per_dev = (bucket // n_dev) * n_chunks * (reps + 1)
    per_sigs = {k: int(v) for k, v in bt.SHARD_SIGS.items()}
    out.update(
        single_sigs_per_s=round(n_sigs / single_dt, 1),
        sharded_sigs_per_s=round(n_sigs / shard_dt, 1),
        speedup=round(single_dt / shard_dt, 2),
        single_warm_s=round(single_warm, 2),
        sharded_warm_s=round(shard_warm, 2),
        bucket=bucket,
        per_device_sigs=per_sigs,
        per_device_occupancy={
            k: round(v / cap_per_dev, 3) for k, v in per_sigs.items()
        },
        devices=info.get("devices"),
        mesh=dict(bt.MESH),
    )
    log(
        f"multichip: {out['sharded_sigs_per_s']:,.1f} sigs/s sharded over "
        f"{n_dev} devices vs {out['single_sigs_per_s']:,.1f} single "
        f"-> {out['speedup']}x"
    )
    return out


def _verifyd_worker(n_sigs: int) -> None:
    """verifyd config, worker half (runs in a subprocess): flood one
    hub with single-signature submissions and report aggregate rate +
    per-signature latency percentiles. With TMTPU_VERIFYD_SOCK in the
    env the hub ships its packed batches to the shared daemon (the
    sidecar shape); without it the worker verifies on its own host.
    The driver pins every worker off the device."""
    import time as _t

    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import verifyd as vdmod
    from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey
    from tendermint_tpu.crypto.verify_hub import VerifyHub

    wid = os.environ.get("_TMTPU_VD_WORKER", "0")
    priv = Ed25519PrivKey(int(wid).to_bytes(4, "big") * 8)
    pub = priv.pub_key()
    tag = b"vd-bench-%s-" % wid.encode()
    items = [(tag + b"%d" % i, priv.sign(tag + b"%d" % i)) for i in range(n_sigs)]

    hub = VerifyHub(window_ms=2.0, cache_size=0)
    hub.start()
    lats: list[float] = []
    bad: list[int] = []
    try:
        futs = []
        t0 = _t.perf_counter()
        for msg, sig in items:
            t_sub = _t.perf_counter()
            fut = hub.submit_nowait(pub, msg, sig)
            fut.add_done_callback(
                lambda f, t=t_sub: lats.append(_t.perf_counter() - t)
            )
            futs.append(fut)
        hub.flush()
        for f in futs:
            if not f.result(timeout=300):
                bad.append(1)
        dt = _t.perf_counter() - t0
    finally:
        hub.stop()
    assert not bad, f"{len(bad)} wrong verdicts"
    # hub.stop() above joined the runner thread that fires the
    # done-callbacks; sorted() copies first anyway, so a straggler
    # append can never corrupt the sort
    lats = sorted(lats)
    p = lambda q: lats[min(len(lats) - 1, int(q * len(lats)))] if lats else 0.0  # noqa: E731
    print(
        "VERIFYD_WORKER_JSON "
        + json.dumps(
            {
                "sigs": n_sigs,
                "dt_s": round(dt, 3),
                "sigs_per_s": round(n_sigs / dt, 1),
                "verify_p50_ms": round(p(0.50) * 1e3, 3),
                "verify_p99_ms": round(p(0.99) * 1e3, 3),
                "remote_dispatches": int(
                    vdmod.CLIENT_STATS["remote_dispatches"]
                ),
                "remote_fallbacks": int(vdmod.CLIENT_STATS["remote_fallbacks"]),
                "attach_attempts": int(bt.BACKEND["attach_attempts"]),
            }
        ),
        flush=True,
    )


def bench_verifyd(
    n_workers: int = 4, sigs_per_worker: int = 1000, timeout_s: float = 600.0
) -> dict:
    """verifyd config driver — BOUNDED, structured outcomes only (hard
    subprocess timeouts). N worker processes flood ONE daemon over its
    UDS, then the same N workers verify on their own hosts; reports
    aggregate sigs/s for both shapes, p50/p99 per-signature verify
    latency, and the daemon's cross-client batch occupancy.

    Who owns the chip: the DAEMON, alone. A chip belongs to one process,
    so this driver must run in a process that has not initialised JAX
    (`python bench.py verifyd`, never from main()), and every worker is
    pinned off the device (JAX_PLATFORMS=cpu + TMTPU_DISABLE_TPU=1): its
    remote route is the socket and its local baseline is the host
    verifier — N processes cannot each attach one chip."""
    import subprocess
    import tempfile

    sock = os.path.join(tempfile.mkdtemp(prefix="vd-bench-"), "vd.sock")
    repo = os.path.dirname(os.path.abspath(__file__))
    base_env = dict(os.environ, PYTHONPATH=repo)

    def run_workers(env_extra: dict) -> list[dict] | str:
        procs = []
        for i in range(n_workers):
            env = dict(base_env, _TMTPU_VD_WORKER=str(i + 1), **env_extra)
            procs.append(
                subprocess.Popen(
                    [
                        sys.executable,
                        "-c",
                        f"import bench; bench._verifyd_worker({sigs_per_worker})",
                    ],
                    env=env,
                    cwd=repo,
                    stdout=subprocess.PIPE,
                    stderr=subprocess.DEVNULL,
                    text=True,
                )
            )
        out = []
        deadline = time.monotonic() + timeout_s
        for p in procs:
            try:
                stdout, _ = p.communicate(
                    timeout=max(1.0, deadline - time.monotonic())
                )
            except subprocess.TimeoutExpired:
                # kill EVERY worker, not just the timed-out one: a
                # leaked sibling would keep flooding through the local
                # baseline pass and skew the A/B this config reports
                for q in procs:
                    if q.poll() is None:
                        q.kill()
                for q in procs:
                    q.wait()
                return f"worker timeout after {timeout_s:.0f}s (bounded)"
            for line in stdout.splitlines():
                if line.startswith("VERIFYD_WORKER_JSON "):
                    out.append(json.loads(line[len("VERIFYD_WORKER_JSON "):]))
        if len(out) != n_workers:
            return f"{len(out)}/{n_workers} workers reported"
        return out

    def agg(records: list[dict]) -> dict:
        wall = max(r["dt_s"] for r in records)
        return {
            "sigs_per_s": round(sum(r["sigs"] for r in records) / wall, 1),
            "verify_p50_ms": round(
                sorted(r["verify_p50_ms"] for r in records)[len(records) // 2], 3
            ),
            "verify_p99_ms": round(max(r["verify_p99_ms"] for r in records), 3),
            "attach_attempts": sum(r["attach_attempts"] for r in records),
            "remote_dispatches": sum(r["remote_dispatches"] for r in records),
            "remote_fallbacks": sum(r["remote_fallbacks"] for r in records),
        }

    out: dict = {"workers": n_workers, "sigs_per_worker": sigs_per_worker}
    daemon_env = dict(base_env)  # the one chip owner: no platform pin
    worker_pin = {"JAX_PLATFORMS": "cpu", "TMTPU_DISABLE_TPU": "1"}
    daemon = subprocess.Popen(
        [
            sys.executable,
            "-c",
            "from tendermint_tpu.cli import main; "
            f"main(['verifyd', '--sock', {sock!r}])",
        ],
        env=daemon_env,
        cwd=repo,
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        from tendermint_tpu.crypto.verifyd import VerifydClient

        stats = None
        # the daemon answers stats as soon as its socket is up; its cold
        # device start (attach + probe + warmup) continues behind that
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            c = VerifydClient(sock)
            stats = c.remote_stats()
            c.close()
            if stats is not None:
                break
            time.sleep(0.5)
        if stats is None:
            out["outcome"] = "daemon never came up (bounded)"
            return out

        remote = run_workers({**worker_pin, "TMTPU_VERIFYD_SOCK": sock})
        c = VerifydClient(sock)
        dstats = c.remote_stats()
        c.close()
    finally:
        daemon.kill()
        daemon.wait()
    local = run_workers(worker_pin)
    if isinstance(remote, str) or isinstance(local, str):
        out["outcome"] = remote if isinstance(remote, str) else local
        return out
    out["remote"] = agg(remote)
    out["local"] = agg(local)
    out["speedup_vs_local"] = round(
        out["remote"]["sigs_per_s"] / max(out["local"]["sigs_per_s"], 1e-9), 2
    )
    if dstats is not None:
        out["daemon"] = {
            "attach_attempts": dstats["backend"]["attach_attempts"],
            "active_kind": dstats["backend"]["active_kind"],
            "requests": dstats["daemon"]["requests"],
            "sigs": dstats["daemon"]["sigs"],
            "shed": dstats["daemon"]["shed"],
            "batch_occupancy": round(dstats["hub"]["mean_occupancy"], 2),
            "cross_client_packs": dstats["hub"]["cross_tenant_dispatches"],
        }
        out["attach_count_sidecar"] = dstats["backend"]["attach_attempts"]
    if out.get("daemon", {}).get("active_kind") != "tpu":
        # the sidecar number is a device number only if the daemon's
        # device served it
        out["outcome"] = f"daemon did not run on a TPU: {out.get('daemon')}"
        return out
    out["outcome"] = "ok"
    log(
        f"verifyd: {out['remote']['sigs_per_s']:,.1f} sigs/s via sidecar "
        f"(occupancy {out.get('daemon', {}).get('batch_occupancy', '?')}, "
        f"{out.get('daemon', {}).get('cross_client_packs', '?')} cross-client "
        f"packs, p99 {out['remote']['verify_p99_ms']}ms) vs "
        f"{out['local']['sigs_per_s']:,.1f} host-only -> {out['speedup_vs_local']}x; "
        f"daemon attaches {out.get('attach_count_sidecar', '?')}"
    )
    return out


def main() -> None:
    import numpy as np

    from tendermint_tpu.crypto.batch import CPUBatchVerifier
    from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
    from tendermint_tpu.crypto.tpu import verify as tpuv

    # the chip first: no TPU -> non-zero exit before any work
    device = require_chip()
    backend = device["platform"]
    reps = 3
    n_commits = int(os.environ.get("TMTPU_BENCH_COMMITS", str(TPU_RANGE_COMMITS)))

    n_vals = 150
    chain_id = "bench-chain"
    log(f"building {n_vals}-validator set + commits …")
    vals, keys, commits, items = _build_commit_items(n_vals, n_commits, chain_id)
    log(f"{len(commits)} commits, {len(items)} signatures")

    # -- CPU baseline -----------------------------------------------------
    base_items = items[: n_vals * 4]
    bv = CPUBatchVerifier(parallel=False)
    for pub, msg, sig in base_items:
        bv.add(Ed25519PubKey(pub), msg, sig)
    t0 = time.perf_counter()
    ok, _ = bv.verify()
    cpu_dt = time.perf_counter() - t0
    assert ok, "CPU baseline verification failed"
    cpu_rate = len(base_items) / cpu_dt
    log(f"CPU baseline (1 thread): {cpu_rate:,.0f} sigs/s ({cpu_dt*1e3:.1f} ms / {len(base_items)})")

    bv = CPUBatchVerifier(parallel=True)
    for pub, msg, sig in base_items:
        bv.add(Ed25519PubKey(pub), msg, sig)
    bv.verify()  # warm the pool
    bv2 = CPUBatchVerifier(parallel=True)
    for pub, msg, sig in base_items:
        bv2.add(Ed25519PubKey(pub), msg, sig)
    t0 = time.perf_counter()
    ok, _ = bv2.verify()
    cpu_mt_dt = time.perf_counter() - t0
    cpu_mt_rate = len(base_items) / cpu_mt_dt
    log(
        f"CPU baseline ({os.cpu_count()} cores): {cpu_mt_rate:,.0f} sigs/s "
        f"({cpu_mt_dt*1e3:.1f} ms / {len(base_items)})"
    )

    # -- TPU path (batch-equation kernel) --------------------------------
    # first call: compile (or persistent-cache load) + execute. A device
    # error here propagates — the run fails, it does not move to the CPU.
    t0 = time.perf_counter()
    bitmap = tpuv.verify_batch_eq(items)
    assert bool(np.all(bitmap)), "verification failed on valid commits"
    compile_s = time.perf_counter() - t0
    log(f"warmup+compile: {compile_s:.1f}s")
    # classify the range-shape compile against the persistent cache
    # (hit ≈ deserialize, well under a second even for the 8192 bucket)
    from tendermint_tpu.crypto import backend_telemetry as _bt

    _bt.record_compile("bench-range", compile_s)

    # rejection path on a SMALL batch (the per-signature fallback kernel
    # compiles at the floor bucket, not the big range bucket)
    t0 = time.perf_counter()
    bad_items = list(items[:64])
    pub0, msg0, sig0 = bad_items[7]
    bad_items[7] = (pub0, msg0, sig0[:63] + bytes([sig0[63] ^ 0x01]))
    bm = tpuv.verify_batch_eq(bad_items)
    assert not bm[7] and bm[:7].all() and bm[8:].all(), "bad-sig bitmap wrong"
    log(f"corrupted-signature rejection: ok ({time.perf_counter()-t0:.1f}s incl fallback compile)")

    t0 = time.perf_counter()
    for _ in range(reps):
        bitmap = tpuv.verify_batch_eq(items)
    tpu_dt = (time.perf_counter() - t0) / reps
    assert bool(np.all(bitmap))
    tpu_rate = len(items) / tpu_dt
    log(f"{backend} end-to-end: {tpu_rate:,.0f} sigs/s ({tpu_dt*1e3:.1f} ms / {len(items)})")

    # -- secondary configs (BASELINE.md 2-5) ------------------------------
    extra = {}
    failed: list[str] = []

    def phase(name: str, fn):
        """Run one config; a raise is logged with its traceback, named in
        the output, and makes the whole run exit non-zero."""
        try:
            extra[name] = fn()
        except Exception:  # noqa: BLE001 — recorded, then fails the run
            import traceback

            log(f"{name} FAILED:\n{traceback.format_exc()}")
            failed.append(name)

    from tendermint_tpu.crypto import batch as crypto_batch

    crypto_batch.tpu_wait_available()
    phase("kernel_breakdown", lambda: kernel_breakdown(items))
    phase("light_headers_per_s", lambda: round(bench_light_client(1000, n_vals), 1))
    phase(
        "blocksync_blocks_per_s",
        lambda: round(bench_blocksync(1024, n_vals, window=TPU_RANGE_COMMITS), 1),
    )
    phase("mixed_commit_sigs_per_s", lambda: round(bench_mixed_commit(n_vals, 4), 1))
    phase("statesync_blocks_per_s", lambda: round(bench_statesync(64, 21), 1))
    # the configs below are host/scheduler work riding on the same
    # process (the device is attached either way); each is bounded
    def env_int(name: str, default: int) -> int:
        return int(os.environ.get(name, str(default)))

    def env_ints(name: str, default: str) -> tuple[int, ...]:
        return tuple(
            int(v) for v in os.environ.get(name, default).split(",") if v.strip()
        )

    def enabled(name: str) -> bool:
        return os.environ.get(name) != "0"

    # verify_hub: the scheduler (coalescing + dedup) against the
    # sequential single-vote path
    phase(
        "verify_hub",
        lambda: bench_verify_hub(
            n_vals, env_int("TMTPU_BENCH_HUB_SUBMITTERS", 8), 200
        ),
    )
    # consensus_ingest: the pipelined receive path (async hub adoption +
    # in-order apply) against the sequential facade on one node
    phase("consensus_ingest", lambda: bench_consensus_ingest(64, 6, 8))
    # tx_flood: the front-door admission pipeline (bounded intake ->
    # batched envelope verify on the hub backfill lane -> nonce lanes ->
    # CheckTx) under a 10k-client open-loop flood
    phase(
        "tx_flood",
        lambda: bench_tx_flood(env_int("TMTPU_BENCH_FLOOD_CLIENTS", 10_000), 2),
    )
    # crash_recovery: WAL repair + replay, pure host work
    phase("crash_recovery", bench_crash_recovery)
    # chaos_soak: blocks/s + time-to-recover per fault scenario over real
    # routers + ChaosTransport (RouterNet) at 4 and 50 validators
    if enabled("TMTPU_BENCH_CHAOS_SOAK"):
        phase(
            "chaos_soak",
            lambda: bench_chaos_soak(env_ints("TMTPU_BENCH_SOAK_VALS", "4,50")),
        )
    # wiregen: the compiled hot codec (consensus/wire_gen.py, regenerated
    # from the wire-schema lockfile by scripts/wiregen) A/B'd against the
    # interpreted codec
    if enabled("TMTPU_BENCH_WIREGEN"):
        phase(
            "wiregen",
            lambda: bench_wiregen(env_int("TMTPU_BENCH_WIREGEN_VALS", 50)),
        )
    # merkle: the HashHub level-order batched tree builder A/B'd against
    # the scalar recursive reference; the device bucket route engages
    # only under TMTPU_HASH_TPU=1
    if enabled("TMTPU_BENCH_MERKLE"):
        phase(
            "merkle", lambda: bench_merkle(env_int("TMTPU_BENCH_MERKLE_VALS", 50))
        )
    # byz_soak: Byzantine strategies over real routers at 4 and 50
    # validators, with the cross-node safety auditor's verdict
    if enabled("TMTPU_BENCH_BYZ_SOAK"):
        phase(
            "byz_soak",
            lambda: bench_byz_soak(env_ints("TMTPU_BENCH_BYZ_VALS", "4,50")),
        )
    # routernet_xl: multi-process committees over real TCP/UDS sockets.
    # Its worker processes are pinned to the CPU (JAX_PLATFORMS=cpu +
    # TMTPU_DISABLE_TPU=1, consensus/routernet_xl._worker_env): none of
    # them wants the chip this process holds.
    if enabled("TMTPU_BENCH_ROUTERNET_XL"):
        phase(
            "routernet_xl",
            lambda: bench_routernet_xl(
                tuple(
                    (int(r.split(":")[0]), int(r.split(":")[1]))
                    for r in os.environ.get("TMTPU_BENCH_XL_ROWS", "50:2").split(",")
                    if r.strip()
                )
            ),
        )
    # commit_ab: EdDSA-batch vs BLS-aggregate on the same 150-validator
    # chain (commit wire bytes x verify sigs/s x catch-up blocks/s)
    phase(
        "commit_ab",
        lambda: bench_commit_ab(env_int("TMTPU_BENCH_AB_VALS", 150), 4),
    )
    # light_fleet: N open-loop light clients syncing genesis→tip against
    # one LightD
    if enabled("TMTPU_BENCH_LIGHT_FLEET"):
        phase(
            "light_fleet",
            lambda: bench_light_fleet(
                env_int("TMTPU_BENCH_LF_VALS", 150),
                env_int("TMTPU_BENCH_LF_CLIENTS", 64),
                env_int("TMTPU_BENCH_LF_HEIGHTS", 6),
            ),
        )
    # statesync: the BootFleet mass-onboarding workload plus the hub
    # backfill-lane per-sig vs bls-aggregate verification A/B
    if enabled("TMTPU_BENCH_STATESYNC"):
        phase(
            "statesync",
            lambda: bench_statesync_fleet(
                env_int("TMTPU_BENCH_SS_BLOCKS", 64),
                env_int("TMTPU_BENCH_SS_VALS", 21),
                env_int("TMTPU_BENCH_SS_JOINERS", 8),
                env_int("TMTPU_BENCH_SS_AB_VALS", 64),
                env_int("TMTPU_BENCH_SS_AB_HEIGHTS", 32),
            ),
        )
    # multichip: sharded vs single-device on the mesh THIS process holds
    # ("not measured" on one device). The verifyd config is NOT here: its
    # daemon must own the chip this process already holds — run it alone
    # with `python bench.py verifyd`.
    if enabled("TMTPU_BENCH_MULTICHIP"):
        phase(
            "multichip",
            lambda: _multichip_measure(env_int("TMTPU_BENCH_MULTICHIP_SIGS", 8192)),
        )
    extra["cpu_multicore_sigs_per_s"] = round(cpu_mt_rate, 1)

    # structured backend-attach phase record: attach latency, the
    # compile/warm split and the persistent-cache outcome per shape are
    # diagnosable from this JSON alone
    from tendermint_tpu.crypto import backend_telemetry as bt

    extra["backend_attach"] = {
        "attach_ms": round(device["attach_s"] * 1e3, 1),
        "compile_ms": round(compile_s * 1e3, 1),  # first-call compile+warm
        "warm_ms": round(tpu_dt * 1e3, 3),  # steady-state warmed call
        # persistent-compile-cache outcome per shape (compile_ms ≈ 0 on
        # a warm cache)
        "compile_cache": {
            "hits": int(bt.BACKEND["compile_cache_hits"]),
            "misses": int(bt.BACKEND["compile_cache_misses"]),
            "per_shape": dict(bt.COMPILE_CACHE),
        },
        "field_mul_probe": dict(tpuv.field_mul_probe),
        "telemetry": bt.snapshot(),
    }
    if failed:
        extra["failed_phases"] = failed

    print(
        json.dumps(
            {
                "metric": "commit sigs verified/sec (150-validator commits, ed25519, range-batched)",
                "value": round(tpu_rate, 1),
                "unit": "sigs/sec",
                "vs_baseline": round(tpu_rate / cpu_rate, 2),
                "device": {k: device[k] for k in ("platform", "kind", "count")},
                "extra": extra,
            }
        ),
        flush=True,
    )
    if failed:
        raise SystemExit(f"bench.py: phases failed: {failed}")


def main_verifyd() -> None:
    """`python bench.py verifyd`: the sidecar config alone. This parent
    never initialises JAX — the daemon it starts is the one process that
    attaches the chip."""
    out = bench_verifyd(
        int(os.environ.get("TMTPU_BENCH_VERIFYD_WORKERS", "4")),
        int(os.environ.get("TMTPU_BENCH_VERIFYD_SIGS", "2000")),
    )
    print(json.dumps({"verifyd": out}), flush=True)
    if out.get("outcome") != "ok":
        raise SystemExit(f"bench.py verifyd: {out.get('outcome')}")


if __name__ == "__main__":
    # any exception: traceback and a non-zero exit — never a "value: 0"
    # line with exit code 0
    if sys.argv[1:] == ["verifyd"]:
        main_verifyd()
    elif sys.argv[1:]:
        raise SystemExit("usage: bench.py [verifyd]")
    else:
        main()
