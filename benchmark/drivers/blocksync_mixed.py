"""Driver `blocksync_mixed`: `blocksync`'s full node catching up on a chain
whose committee mixes key types (configuration `mixedfull150`: 75 ed25519 +
75 secp256k1).

Install, window and release are `blocksync`'s, unchanged: the same entry
(`BlockSyncReactor`, start -> stop, as `node.Node` wires it), the same peer
stand-ins, hub and stores. Fixture, warm-up and the comparison are this
driver's, because the plain reference differs (`reference_mixedfull`: every
signature under its own key's scheme) and because a mixed set BEHIND THE HUB
can go wrong in ways neither an ed25519 one nor a mixed one without a hub
can: a range enters the hub as one group of every key type, and what the hub
does with the rows that have no batch kernel is the deployment's question.
Beside `blocksync`'s checks, each exact, from the program's own route
counters (`backend_telemetry.ROUTES`) over the window and its stretch:

  edwards_sigs_on_device_minus_range_needed
        route `tpu` carried the Edwards rows the reference needs for the
        window's verify calls, no more and no fewer. A call whose Edwards
        rows fall under the process's measured cut-off `MIN_TPU_BATCH` (a
        chain's one- or two-commit tail) is expected on the host: the check
        reads the cut-off, it does not rest on where it landed (trap 1).
  ecdsa_sigs_routed_minus_needed
        every secp256k1 row the reference needs was verified under its own
        scheme on a COUNTED ECDSA route: `host-ecdsa` plus any route whose
        name carries the scheme (`ecdsa`, `secp256k1` — a device kernel's,
        when there is one), summed. A hub that verifies such rows off every
        counted route (a loop of its own) reads low by all of them, a lane
        that skips rows low, one that runs twice high. Rows the hub's verdict
        LRU answered (`hub.cache_hits`, `hub.coalesced`: none in a one-pass
        sync) were verified before and are allowed to be missing.
  warmup_refusal_height_delta.edwards / .ecdsa
        the warm-up chain is synced TWICE, each time with one bit flipped in
        one signature among the last tenth of the quorum — once on an Edwards
        row (the batch equation fails, the per-signature program attributes
        it), once on an ECDSA row (the host lane finds it) — and refused at
        exactly that height, which the reference refuses too.
  warmup_other_faults
        nothing else refused, both syncs in order to the reference's app hash.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from benchmark import fixtures, fixtures_mixed, fixtures_mixedfull, harness
from benchmark import reference_mixedfull as refmf
from benchmark.drivers import blocksync as base
from benchmark.drivers import light_mixed
from benchmark.harness import Check, say

END_TO_END = base.END_TO_END
FIXTURE = base.FIXTURE
install = base.install
window = base.window
release = base.release

# the lanes by key type, and which counted routes verify secp256k1 rows under
# their own scheme: the light twin's, as they stand
LANES = light_mixed.LANES
ecdsa_routed = light_mixed.ecdsa_routed


@dataclass
class Fixture:
    warm: fixtures.KVChain
    warm_bad: dict  # lane -> (height whose commit is corrupted, validator index)
    chain: fixtures.KVChain | None = None  # the window's: handed over after the rest
    observed: dict = field(default_factory=dict)
    hub: object = None


def build(cfg: dict, cell: dict, seed: int):
    """`blocksync.build`'s order and protocol: the warm-up chain and its two
    corruptions first, then the window's chain, both over the pinned mixed
    set (`fixtures_mixedfull`)."""
    p, v = cell["traffic"], cfg["validators"]
    key_types = tuple(v["key_types"])
    if p["key_types"] != ",".join(key_types):
        raise RuntimeError(f"cell states key_types {p['key_types']!r}, the configuration "
                           f"{','.join(key_types)!r}")

    def chain(tag: str, blocks: int) -> fixtures.KVChain:
        return asyncio.run(fixtures_mixedfull.kvstore_chain(
            seed, tag, blocks, v["count"], v["power"], p["txs_per_block"], key_types))

    t0 = time.perf_counter()
    n = p["warmup_blocks"]
    warm = chain("mfwarm", n)
    _ok, needed, _bad, by_scheme = refmf.commit_verdict(warm.commit_data(1))
    mix = " + ".join(f"{by_scheme[t]} {t}" for t in key_types)
    if p["quorum_mix"] != mix:
        raise RuntimeError(f"cell states quorum_mix {p['quorum_mix']!r}, the seeded set's "
                           f"first > 2/3 hold {mix!r}")
    rows = fixtures_mixed.quorum_rows(warm.vals, needed)
    warm_bad = {
        lane: (fixtures.seeded_index(seed, f"mfbadh-{lane}", 8, min(40, n - 8)),
               fixtures_mixed.seeded_bad_index(seed, f"mfbadi-{lane}", rows[scheme], needed))
        for lane, scheme in LANES.items()
    }
    fx = Fixture(warm=warm, warm_bad=warm_bad)
    say(f"mixedfull: built {n}-block warm-up chain ({v['count']} validators of {key_types}, "
        f"{p['txs_per_block']} txs a block; {needed} signatures reach > 2/3: {mix}) in "
        f"{time.perf_counter() - t0:.1f}s; warm-up corruptions (commit for height, "
        f"signature) {warm_bad}")
    yield fx
    t0 = time.perf_counter()
    sync = chain("mfsync", p["blocks"])
    say(f"mixedfull: built {p['blocks']}-block chain in {time.perf_counter() - t0:.1f}s, "
        f"{sum(map(len, sync.wire.values()))} wire bytes")
    yield {"chain": sync}


def _warm_short_range_shapes(fx: Fixture) -> list[str]:
    """The shapes a SHORT range reaches. A range of at most `max_batch` rows
    (5 commits or fewer: the pool's first hand-over, the re-fetch after a
    refusal, a chain's tail) is not sent at the chunk shape: its Edwards rows
    — 50 a commit, over the quorum's 50 Edwards keys — take the ladder rung of
    their own count at the keys' own group bucket (256, 128 or 64 rows at
    gb63), where they reach the process's measured cut-off. Which rungs that
    lets through is read from `MIN_TPU_BATCH`, as `blocksync._warm_shapes`
    reads it; none of them is a shape the node's start-up warms, and a cold
    compile of one is minutes: inside a warm-up sync it would run out the
    sync's 120 s (first reading of this cell: 92.7 s, PERF.md §6), inside the
    window it would stall a run."""
    from tendermint_tpu.crypto import batch as cb

    if not cb.tpu_verifier_available():
        return ["none: no device route in this process"]
    ed = [i for i, v in enumerate(fx.warm.vals.validators)
          if v.pub_key.TYPE == refmf.ED25519][:refmf.commit_verdict(
              fx.warm.commit_data(1))[3][refmf.ED25519]]
    warmed, done = [], set()
    for commits in range(1, 6):
        rows = commits * len(ed)
        bucket = 64
        while bucket < rows:
            bucket *= 2
        if rows < cb.MIN_TPU_BATCH or bucket in done:
            continue
        done.add(bucket)
        items = []
        for h in range(1, commits + 1):
            c = fx.warm.commit(h)
            items += [(fx.warm.vals.validators[i].pub_key, c.vote_sign_bytes(fx.warm.chain_id, i),
                       c.signatures[i].signature) for i in ed]
        t0 = time.perf_counter()
        bv = cb.AdaptiveBatchVerifier()
        bv.add_many(items)
        ok, _ = bv.verify()
        if not ok or bv.last_route != "tpu":
            raise RuntimeError(f"warm-up of a {commits}-commit range's {rows} Edwards rows: "
                               f"ok={ok} route={bv.last_route}")
        warmed.append(f"eq {bucket}/gb63 ({rows} Edwards rows of {commits} commits, "
                      f"{time.perf_counter() - t0:.1f}s)")
    return warmed


def warmup(fx: Fixture, cfg: dict, cell: dict, spans: harness.Spans) -> list[str]:
    """Drive the warm-up chain (other chain ID and keys) through the reactor
    twice, each time behind a hub acquired anew, one stand-in serving one
    corrupted commit each time (a sync of its own each, as `blocksync_churn`
    does and for its reason); the second hub stays for the window. A whole
    range compiles nothing of its own: it is one group of more rows than
    `max_batch`, so its Edwards rows go out at the program the node's
    start-up warms last (8192 rows, gb255: `[max]`), the refusal's attribution
    is the per-signature program of that chunk (start-up's too), and the
    ECDSA rows run no program. What is warmed first is what a SHORT range
    reaches (`_warm_short_range_shapes`); NOT `blocksync._warm_shapes`'
    eq 512 / 256 / 128 at gb127, which no dispatch of this cell takes."""
    shapes = _warm_short_range_shapes(fx)
    say(f"mixedfull warm-up: short-range shapes {shapes}")
    want_hash = {}
    for lane, (h, index) in fx.warm_bad.items():
        # a fresh hub a sync, as a fresh node has: the first sync's verdict LRU
        # would answer most of the second's rows, and a flipped row left alone
        # in its dispatch is verified directly, never by the lane under test
        release(fx)
        fx.hub = base.acquire_hub(cfg)
        t0 = time.perf_counter()
        s = asyncio.run(base._sync(fx.warm, cell, 120.0, spans,
                                   bad=base._bad_wire(fx.warm, h, index)))
        if s.final_height not in want_hash:
            want_hash[s.final_height] = refmf.kv_state_hash(
                [tx for hh in range(1, s.final_height + 1) for tx in fx.warm.txs_at[hh]])
        ok = (s.final_height >= fx.warm.n_blocks - 1
              and s.app_hash == want_hash[s.final_height]
              and all(hh == i + 1 for i, hh in enumerate(s.applied)))
        fx.observed[lane] = {"refused": s.refused, "synced_in_order": ok,
                             "peer_errors": s.peer_errors}
        say(f"mixedfull warm-up ({lane}): synced {s.final_height}/{fx.warm.n_blocks} in "
            f"{time.perf_counter() - t0:.1f}s, refused heights {s.refused} (served bad: "
            f"signature {index} of the commit for {h}), app hash and order ok {ok}")
    return shapes + [
        "eq 8192/gb255 (the start-up's [max]: a range's Edwards rows, one dispatch)",
        "per-signature 8192 (the Edwards refusal's attribution)",
        "host lane (a range's ECDSA rows: no program)"]


def _short_by(have: float, want: float, slack: float) -> float:
    """How far `have` is from `want`; up to `slack` rows may be missing
    (answered without a verify), none may be over."""
    return have - want if have > want else max(0.0, (want - have) - slack)


def compare(fx: Fixture, w, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    """`blocksync.compare` against `reference_mixedfull`, with the signature
    counts taken apart by scheme and by route. Returns (checks, attempted,
    failed)."""
    from tendermint_tpu.crypto import batch as cb

    s = w.sync
    ranges = [(r[3]["first"], r[3]["n"], r[3].get("failed_index"))
              for r in spans.select("verify") if r[1] >= w.t0]
    rr = refmf.read_ranges(fx.chain.commit_data, ranges)
    # the reference refuses nothing here
    mismatches = rr.mismatches + len(s.peer_errors) + len(s.refused)
    asked = (d.get("hub.submitted", 0.0) + d.get("hub.cache_hits", 0.0)
             + d.get("hub.coalesced", 0.0))
    answered = d.get("hub.cache_hits", 0.0) + d.get("hub.coalesced", 0.0)
    # a verify call is one group and one dispatch: its Edwards rows ride the
    # device where they reach the process's measured cut-off
    ed, ec = refmf.ED25519, refmf.SECP256K1
    want_device = sum(call[ed] for call in rr.by_call if call[ed] >= cb.MIN_TPU_BATCH)
    under = sum(1 for call in rr.by_call if 0 < call[ed] < cb.MIN_TPU_BATCH)
    on_device = d.get("route.tpu.sigs", 0.0)
    by_ecdsa_route = ecdsa_routed(d)
    on_lane = sum(by_ecdsa_route.values())
    say(f"mixedfull: {len(ranges)} verify calls of "
        f"{min((n for _f, n, _x in ranges), default=0)}-"
        f"{max((n for _f, n, _x in ranges), default=0)} commits; asked {asked:.0f} signatures "
        f"({answered:.0f} answered without a verify), the reference needs {rr.needed}: "
        f"{rr.needed_of(ed)} Edwards + {rr.needed_of(ec)} ECDSA; route tpu {on_device:.0f} "
        f"(calls at or over the cut-off {cb.MIN_TPU_BATCH} need {want_device}, {under} calls "
        f"under it); ECDSA routes {by_ecdsa_route} = {on_lane:.0f}; hub host-lane rows "
        f"{d.get('hub.scheme_host_sigs', 0.0):.0f}")
    checks = [
        Check("verdict_mismatches", mismatches, 0),
        Check("apply_order_faults", refmf.apply_order_faults(s.applied, s.final_height), 0),
        Check("stored_mismatches",
              refmf.stored_mismatches(s.stored_hashes, fx.chain.block_hash_at, s.final_height), 0),
        Check("app_hash_mismatch", refmf.app_hash_mismatch(
            s.app_hash, fx.chain.txs_at, s.final_height,
            fx.chain.app_hash_at.get(s.final_height)), 0),
        Check("sigs_asked_minus_needed", abs(asked - rr.needed), 0),
        Check("edwards_sigs_on_device_minus_range_needed",
              _short_by(on_device, want_device, answered), 0),
        Check("ecdsa_sigs_routed_minus_needed",
              _short_by(on_lane, rr.needed_of(ec), answered), 0),
    ]
    # warm-up: each corrupted commit refused at exactly its height — where the
    # reference refuses it, and only it — nothing else refused, both syncs in
    # order to the reference's app hash
    other = 0
    for lane, (h, index) in fx.warm_bad.items():
        forged = fixtures.commit_data(
            fx.warm.chain_id,
            fixtures.corrupt_commit(fx.warm.block(h + 1).last_commit, index), fx.warm.vals)
        ref_refuses = refmf.first_refused([fx.warm.commit_data(h), forged]) == 1
        seen = fx.observed.get(lane, {})
        refused = seen.get("refused", [])
        near = min((abs(r - h) for r in refused), default=fx.warm.n_blocks)
        checks.append(Check(f"warmup_refusal_height_delta.{lane}",
                            near + int(not ref_refuses), 0))
        other += max(0, len(refused) - 1) + int(not seen.get("synced_in_order", False))
    checks += [
        Check("warmup_other_faults", other, 0),
        Check("blocks_applied", w.units, 1, "min"),
    ]
    return checks + harness.device_served_checks(d), rr.attempted, rr.failed
