"""Driver `blocksync_churn`: `blocksync`'s full node catching up on a chain
whose committee CHANGES every few heights (configuration `churn150`).

Install, window and release are `blocksync`'s, unchanged: the same entry
(`BlockSyncReactor`, start -> stop, as `node.Node` wires it), the same peer
stand-ins, hub and stores. Fixture, warm-up and the comparison are this
driver's, because the plain reference differs (`reference_churn`: it derives
the validator set of EVERY height itself, from the genesis set and the
chain's `val:` transactions, and holds each commit to the set of its own
height) and because a changing set can go wrong in ways a static one cannot.
Beside `blocksync`'s checks, each exact:

  valset_hash_mismatches
        at the last height applied and 33 seeded heights, the set the node's
        state store holds and the two set hashes its stored header names,
        against the reference's derivation.
  sequential_blocks
        commits the reactor verified ONE AT A TIME (its `verify_commit_light`,
        the fallback for a planned call that failed against its true sets):
        0 on honest traffic. Counted at the call, whatever route it took.
  plans_minus_expected
        over the runs the window handed to `_verify_and_apply`: the verify
        calls each run took against those the reference's rule gives (plan
        while a height's set is one of the two the state holds, cut at a
        third, plan again), first height and length of each. A run verified
        against one stale set, or one commit at a time, reads high.
  warmup_refusal_height_delta.bitflip / .stale_set
        the warm-up chain is refused TWICE at exactly its height: once a
        flipped signature bit among the last tenth of the quorum, once a
        commit for the first height of a set made by a power change, signed
        in order by every validator of the set of the height BEFORE — which
        the reference accepts under that stale set and refuses under the true
        one (asserted when the fixture is built).
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from dataclasses import dataclass, field

from benchmark import fixtures, fixtures_churn, harness
from benchmark import reference as ref
from benchmark import reference_churn as refc
from benchmark.drivers import blocksync as base
from benchmark.harness import Check, say

END_TO_END = base.END_TO_END
FIXTURE = base.FIXTURE
window = base.window
release = base.release

#: what `install` hangs on the program for one run: the nodes `_sync` made
#: (fresh_node's return, newest last) and the reactor's one-commit verifies
_SEEN = {"nodes": [], "singles": []}


@dataclass
class Fixture:
    warm: fixtures_churn.ChurnChain
    warm_bad: dict  # kind -> height whose commit the stand-in serves corrupted
    warm_bad_index: int  # the signature the bitflip flips
    warm_stale_commit: object  # the forged commit for warm_bad["stale_set"]
    sample_heights: list
    chain: fixtures_churn.ChurnChain | None = None  # the window's: handed over after the rest
    observed: dict = field(default_factory=dict)
    hub: object = None


def build(cfg: dict, cell: dict, seed: int):
    """`blocksync.build`'s order and protocol: the warm-up chain, its two
    corruptions and the stale-set commit (signed here: the private keys do
    not leave the process that made them), then the window's chain."""
    p, v, rot = cell["traffic"], cfg["validators"], cfg["rotation"]
    for key, want in (("rotation_period", rot["period"]), ("swap_every", rot["swap_every"])):
        if p[key] != want:
            raise RuntimeError(f"cell states {key} {p[key]}, the configuration {want}")

    def churn_chain(tag: str, blocks: int) -> fixtures_churn.ChurnChain:
        return asyncio.run(fixtures_churn.churn_chain(
            seed, tag, blocks, v["count"], v["power"], p["txs_per_block"],
            p["rotation_period"], p["swap_every"]))

    t0 = time.perf_counter()
    warm = churn_chain("cwarm", p["warmup_blocks"])
    n = p["warmup_blocks"]
    flip_h = fixtures.seeded_index(seed, "cbadh", 8, max(8, min(40, n // 2 - 4)))
    needed = ref.commit_verdict(warm.commit_data(flip_h))[1]
    # the stale-set commit: a seeded one of the heights at which a power
    # change's set first holds that TELLS the two sets apart (a mover that
    # already sat first moves nobody)
    firsts = [h for h in warm.first_heights_of_power_sets() if h < n - 1]
    start = fixtures.seeded_index(seed, "cstale", 0, max(0, len(firsts) - 1))
    stale_h = next((h for h in firsts[start:] + firsts[:start]
                    if fixtures_churn.stale_commit_is_telling(warm, h)), None)
    if stale_h is None:
        raise RuntimeError(f"seed {seed}: no power change of the warm-up chain moves a "
                           f"validator the quorum reads (first heights {firsts})")
    fx = Fixture(
        warm=warm.shed(),
        warm_bad={"bitflip": flip_h, "stale_set": stale_h},
        warm_bad_index=fixtures.seeded_index(
            seed, "cbadi", needed - max(1, needed // 10), needed - 1),
        warm_stale_commit=warm.stale_commit(stale_h),
        sample_heights=sorted({fixtures.seeded_index(seed, f"cs{i}", 1, p["blocks"])
                               for i in range(33)}),
    )
    say(f"churn: built {n}-block warm-up chain ({v['count']} validators, "
        f"{p['txs_per_block']} txs a block) in {time.perf_counter() - t0:.1f}s; warm-up "
        f"corruptions: bit {fx.warm_bad_index} of the commit for height {flip_h} ({needed} "
        f"signatures reach > 2/3), stale-set commit for height {stale_h}")
    yield fx
    t0 = time.perf_counter()
    chain = churn_chain("churn", p["blocks"]).shed()
    kinds = sorted(chain.changes.values())
    say(f"churn: built {p['blocks']}-block chain in {time.perf_counter() - t0:.1f}s, "
        f"{sum(map(len, chain.wire.values()))} wire bytes; {kinds.count('power')} power changes "
        f"and {kinds.count('swap')} swaps, {len({s.hash for s in chain.sets[1:]})} distinct sets, "
        f"{min(sum(s.powers) for s in chain.sets[1:])}-{max(sum(s.powers) for s in chain.sets[1:])} "
        f"total power")
    yield {"chain": chain}


def install(patches: harness.Patches, spans: harness.Spans, traced: bool) -> None:
    """`blocksync`'s spans, and beside them: a `run` span around each run the
    reactor verifies and applies (first height, blocks), a count of its
    one-commit verifies, and a hold on the node `_sync` builds (its stores
    are what `valset_hash_mismatches` reads)."""
    from tendermint_tpu.blocksync import reactor

    base.install(patches, spans, traced)
    _SEEN["nodes"].clear()
    _SEEN["singles"].clear()

    def make_run(orig):
        async def wrapped(self, run, *a, **kw):
            with spans.span("run", first=run[0][0].header.height, n=len(run) - 1) as attrs:
                out = await orig(self, run, *a, **kw)
                attrs["done"] = True  # not cut short by the reactor's stop
                return out

        return wrapped

    patches.wrap(reactor.BlockSyncReactor, "_verify_and_apply", make_run)

    def make_single(orig):
        def wrapped(chain_id, vals, block_id, height, commit, **kw):
            _SEEN["singles"].append((time.perf_counter(), height))
            return orig(chain_id, vals, block_id, height, commit, **kw)

        return wrapped

    patches.wrap(reactor, "verify_commit_light", make_single)

    def make_node(orig):
        async def wrapped(genesis):
            node = await orig(genesis)
            _SEEN["nodes"].append(node)
            return node

        return wrapped

    patches.wrap(fixtures, "fresh_node", make_node)


def _bad_wire(fx: Fixture, kind: str) -> dict:
    """The block the byzantine stand-in serves once: the block AFTER the bad
    height, its LastCommit (the commit FOR that height) replaced."""
    from tendermint_tpu.blocksync import messages as bsm

    h = fx.warm_bad[kind]
    nxt = fx.warm.block(h + 1)
    forged = (fixtures.corrupt_commit(nxt.last_commit, fx.warm_bad_index)
              if kind == "bitflip" else fx.warm_stale_commit)
    return {h + 1: bsm.encode_message(
        bsm.BlockResponse(dataclasses.replace(nxt, last_commit=forged)))}


def warmup(fx: Fixture, cfg: dict, cell: dict, spans: harness.Spans) -> list[str]:
    """Acquire the hub and warm every dispatch shape the cut-off lets a SHORT
    plan reach (`blocksync`'s 512, 256, 128, 64 at gb127; since PR 39 a plan of
    15-17 commits is ONE hub group and one 8192/gb255 dispatch), then
    drive the warm-up chain (other chain ID and keys, the same schedule)
    through the reactor twice, one stand-in serving one corrupted commit each
    time (a sync of its own each: a refusal drops the blocks its two
    providers served, and with them a second corruption served once)."""
    fx.hub = base.acquire_hub(cfg)
    shapes = base._warm_shapes(fx)
    say(f"churn warm-up: shapes {shapes}")
    want_hash = {}
    for kind, h in fx.warm_bad.items():
        t0 = time.perf_counter()
        s = asyncio.run(base._sync(fx.warm, cell, 120.0, spans, bad=_bad_wire(fx, kind)))
        if s.final_height not in want_hash:
            want_hash[s.final_height] = refc.kv_state_hash(
                [tx for hh in range(1, s.final_height + 1) for tx in fx.warm.txs_at[hh]])
        ok = (s.final_height >= fx.warm.n_blocks - 1
              and s.app_hash == want_hash[s.final_height]
              and all(hh == i + 1 for i, hh in enumerate(s.applied)))
        fx.observed[kind] = {"refused": s.refused, "synced_in_order": ok,
                             "peer_errors": s.peer_errors}
        say(f"churn warm-up ({kind}): synced {s.final_height}/{fx.warm.n_blocks} in "
            f"{time.perf_counter() - t0:.1f}s, refused heights {s.refused} (served bad: the "
            f"commit for {h}), app hash and order ok {ok}")
    shapes.append("per-signature 512 (the refusals' attribution)")
    return shapes


def _valset_mismatches(fx: Fixture, s, node) -> int:
    _app, _conns, bstore, _state, ex = node
    bad = 0
    for h in sorted({h for h in fx.sample_heights if h <= s.final_height} | {s.final_height}):
        if h < 1:
            continue
        want, want_next = fx.chain.sets[h].hash, fx.chain.sets[h + 1].hash
        held = ex.state_store.load_validators(h)
        meta = bstore.load_block_meta(h)
        bad += held is None or held.hash() != want
        bad += meta is None or meta.header.validators_hash != want
        bad += meta is None or meta.header.next_validators_hash != want_next
    return bad


def compare(fx: Fixture, w, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    """`blocksync.compare` against `reference_churn` — every commit held to
    the reference's own set of its height — and the checks at the head of
    this file. Returns (checks, attempted, failed)."""
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
    want_hash = refc.kv_state_hash(
        [tx for h in range(1, s.final_height + 1) for tx in fx.chain.txs_at[h]])
    app_bad = int(s.app_hash != want_hash) + int(
        s.final_height > 0 and fx.chain.app_hash_at[s.final_height] != want_hash)
    asked = (d.get("hub.submitted", 0.0) + d.get("hub.cache_hits", 0.0)
             + d.get("hub.coalesced", 0.0))

    valset_bad = _valset_mismatches(fx, s, _SEEN["nodes"][-1]) if _SEEN["nodes"] else 1
    singles = sum(1 for t, _h in _SEEN["singles"] if t >= w.t0)
    # the verify calls of each run, against the reference's plan of that run
    calls = sorted((first, n) for first, n, _f in ranges)
    plan_faults = 0
    runs = [(r[3]["first"], r[3]["n"], r[3].get("done", False))
            for r in spans.select("run") if r[1] >= w.t0]
    for first, n, done in runs:
        mine = [c for c in calls if first <= c[0] < first + n]
        want = refc.expected_plans(fx.chain.sets, first, n)
        if not done:  # the run the stop cut short: what it got to, as planned
            want = want[:len(mine)]
        plan_faults += len(set(mine) ^ set(want)) + abs(len(mine) - len(want))
    plan_faults += sum(1 for c in calls if not any(f <= c[0] < f + n for f, n, _d in runs))
    say(f"churn: {len(runs)} runs, {len(calls)} verify calls of "
        f"{min((n for _f, n in calls), default=0)}-{max((n for _f, n in calls), default=0)} "
        f"commits, {singles} one-commit verifies; asked {asked:.0f} signatures, the reference "
        f"needs {needed} under each height's own set")

    checks = [
        Check("verdict_mismatches", mismatches, 0),
        Check("apply_order_faults", order_faults, 0),
        Check("stored_mismatches", stored_bad, 0),
        Check("app_hash_mismatch", app_bad, 0),
        Check("sigs_asked_minus_needed", abs(asked - needed), 0),
        Check("valset_hash_mismatches", valset_bad, 0),
        Check("sequential_blocks", singles, 0),
        Check("plans_minus_expected", plan_faults, 0),
    ]
    # warm-up: each corrupted commit refused at exactly its height, nothing
    # else refused, the chain synced in order to the reference's app hash
    other = 0
    for kind, h in fx.warm_bad.items():
        seen = fx.observed.get(kind, {})
        refused = seen.get("refused", [])
        near = min((abs(r - h) for r in refused), default=fx.warm.n_blocks)
        checks.append(Check(f"warmup_refusal_height_delta.{kind}", near, 0))
        other += max(0, len(refused) - 1) + int(not seen.get("synced_in_order", False))
    checks += [
        Check("warmup_other_faults", other, 0),
        Check("blocks_applied", w.units, 1, "min"),
    ]
    return checks + harness.device_served_checks(d), attempted, failed
