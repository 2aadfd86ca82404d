"""Driver `light_mixed`: `light_sequential`'s light clients following a chain
whose committee mixes key types (ed25519 + secp256k1).

Spans, window and release are `light_sequential`'s, unchanged: the same
entry (`light.LightClient.verify_light_block_at_height`, sequential mode,
no hub), the same traffic. Fixture, warm-up and the comparison are this
driver's, because the plain reference differs (`reference_mixed`: every
signature under its own key's scheme) and because a mixed set can go wrong
in ways an ed25519 one cannot. Each is an exact check on the program's own
route counters (`backend_telemetry.ROUTES`), read from the window's opening
to the end of the run:

  edwards_sigs_on_device_minus_range_needed
        route `tpu` carried the Edwards rows the reference needs for the
        RANGE calls, no more and no fewer: the Edwards majority rides the
        range batch to the chip although its neighbours cannot. (A
        session's trusted-header commit holds ~50 Edwards rows; they are
        expected on the device only where the process's measured cut-off
        `MIN_TPU_BATCH` reaches that low — the check reads the cut-off, it
        does not rest on where it landed.)
  ecdsa_sigs_on_host_minus_needed
        every secp256k1 row the reference needs was verified under its own
        scheme on a COUNTED ECDSA route: `host-ecdsa` plus any route whose
        name carries the scheme (`ecdsa`, `secp256k1` — a device kernel's,
        when there is one), summed. A lane that skips rows reads low, one
        that runs twice high; a row counted on an Edwards route (`tpu`,
        `cpu`) fails here and in the check above, a row counted on two
        routes in `sigs_verified_minus_needed`. Until PR 38 this read
        `host-ecdsa` alone and so forbade a device kernel; the name keeps
        its `on_host` because tier-1's `tests/test_mixed150.py` reads the
        check by it (PERF.md §7: `ecdsa_sigs_routed_minus_needed` is the
        name it should take, in a PR that may edit both).
  warmup_refusal_height_delta.edwards / .ecdsa
        the warm-up chain with one bit flipped in a signature among the
        last tenth of the quorum — once on an Edwards row, once on an
        ECDSA row — is refused at exactly that height, whichever lane has
        to find it.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from benchmark import fixtures, fixtures_mixed, harness
from benchmark import reference_mixed as refm
from benchmark.drivers import light_sequential as base
from benchmark.harness import Check, say

END_TO_END = base.END_TO_END
install = base.install
window = base.window
release = base.release

#: a lane of the verifier -> the key type whose rows take it
LANES = {"edwards": refm.ED25519, "ecdsa": refm.SECP256K1}
#: what a route's name carries where its rows are verified as ECDSA over secp256k1
ECDSA_ROUTE_MARKS = ("ecdsa", "secp256k1")


def ecdsa_routed(d: dict) -> dict:
    """route -> signatures, of the counted routes that verify secp256k1 rows
    under their own scheme (`d`: the run's counter deltas)."""
    routes = {k[len("route."):-len(".sigs")]: v for k, v in d.items()
              if k.startswith("route.") and k.endswith(".sigs") and v}
    return {route: v for route, v in routes.items()
            if any(mark in route for mark in ECDSA_ROUTE_MARKS)}


@dataclass
class Fixture:
    chain: fixtures.LightChain
    warm: fixtures.LightChain
    warm_bad: dict  # lane -> (height, validator index) of its corrupted signature
    sample_heights: list
    observed: dict = field(default_factory=dict)


def build(cfg: dict, cell: dict, seed: int):
    """Yields the whole fixture once, as `light_sequential.build` does."""
    p, v = cell["traffic"], cfg["validators"]
    key_types = tuple(v["key_types"])
    chain = fixtures_mixed.light_chain(seed, "mixed", p["headers"], v["count"], v["power"],
                                       key_types)
    warm = fixtures_mixed.light_chain(seed, "mwarm", p["warmup_headers"], v["count"],
                                      v["power"], key_types)
    _ok, needed, _bad, by_scheme = refm.commit_verdict(warm.commit_data(1))
    rows = fixtures_mixed.quorum_rows(warm.vals, needed)
    warm_bad = {}
    for lane, scheme in LANES.items():
        if not rows.get(scheme):
            raise RuntimeError(f"seed {seed}: no {scheme} row among the {needed} the quorum needs")
        warm_bad[lane] = (
            fixtures.seeded_index(seed, f"mbadh-{lane}", 2, min(17, p["warmup_headers"])),
            fixtures_mixed.seeded_bad_index(seed, f"mbadi-{lane}", rows[scheme], needed),
        )
    fx = Fixture(
        chain=chain,
        warm=warm,
        warm_bad=warm_bad,
        sample_heights=sorted(
            {fixtures.seeded_index(seed, f"ms{i}", 2, p["headers"]) for i in range(32)}
            | {1, p["headers"]}
        ),
    )
    say(f"mixed: built {p['headers']}-header chain + {p['warmup_headers']}-header warm-up "
        f"chain, {v['count']} validators of {key_types}; on the warm-up chain {needed} "
        f"signatures reach > 2/3: {by_scheme}; warm-up corruptions (height, signature) "
        f"{warm_bad}")
    yield fx


def warmup(fx: Fixture, cfg: dict, cell: dict, spans: harness.Spans) -> list[str]:
    """One clean session over the warm-up chain (other chain ID and keys):
    its one 128-header window is the window's one dispatch shape — the
    Edwards rows of 128 commits in one 8192-row chunk over ~50 keys — and
    its ECDSA rows run down the host lane. Then the same chain twice more,
    each with one corrupted commit the client must refuse at exactly that
    height: an Edwards row (the equation fails: the per-signature program
    attributes it) and an ECDSA row (the lane finds it)."""
    from tendermint_tpu.light.verifier import VerificationError

    base._assert_no_hub()
    n = len(fx.warm.blocks)
    t0 = time.perf_counter()
    asyncio.run(base._client(fx.warm, fx.warm.blocks)
                .verify_light_block_at_height(n, fx.warm.now_ns))
    t1 = time.perf_counter()
    refused = fx.observed["warm_refused_at"] = {}
    for lane, (height, index) in fx.warm_bad.items():
        bad = fixtures.with_corrupt_header(fx.warm, height, index)
        refused[lane] = -1
        try:
            asyncio.run(base._client(fx.warm, bad).verify_light_block_at_height(n, fx.warm.now_ns))
        except VerificationError as e:
            refused[lane] = base._refused_height(e, -1)
            say(f"mixed warm-up: corrupted {lane} row refused: {str(e)[:120]}")
    by_scheme = refm.commit_verdict(fx.warm.commit_data(1))[3]
    ed, ec = by_scheme[refm.ED25519], by_scheme[refm.SECP256K1]
    gb = 64
    while gb < ed + 1:
        gb *= 2
    shapes = [f"eq 8192/gb{gb - 1} x{-(-(n - 1) * ed // 8192)} ({(n - 1) * ed} Edwards "
              f"signatures, {ed} keys)",
              "per-signature 8192 (the Edwards refusal's attribution)",
              f"host lane ({(n - 1) * ec} ECDSA signatures a window: no program)"]
    say(f"mixed warm-up: clean session {t1 - t0:.1f}s, two refusal sessions "
        f"{time.perf_counter() - t1:.1f}s; warmed {shapes}")
    return shapes


def _ref_refusal_height(fx: Fixture, height: int, index: int) -> int:
    """Where the reference refuses the warm-up chain corrupted at
    (height, index): the first commit from height 2 on that it rejects."""
    blocks = fixtures.with_corrupt_header(fx.warm, height, index)[1:height]
    verdicts = refm.commit_verdicts(
        [fixtures.commit_data(fx.warm.chain_id, lb.signed_header.commit, lb.validators)
         for lb in blocks])
    return next((i + 2 for i, v in enumerate(verdicts) if not v[0]), -1)


def compare(fx: Fixture, w, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    """`light_sequential.compare` against `reference_mixed`, with the
    signature counts taken apart by scheme and by route. Returns (checks,
    attempted, failed)."""
    from tendermint_tpu.crypto import batch as cb

    heights = sorted({h for _s, a, b, _r in w.calls for h in range(a, b + 1)} | {1})
    verdicts = dict(zip(heights, refm.commit_verdicts(
        [fx.chain.commit_data(h) for h in heights])))
    mismatches = attempted = failed = needed = 0
    in_ranges = {scheme: 0 for scheme in LANES.values()}
    for _s, a, b, refused_at in w.calls:
        for h in range(a, b + 1):
            if refused_at and h > refused_at:
                break
            attempted += 1
            accepted = not (refused_at and h == refused_at)
            failed += not accepted
            ok, checked, _bad, by_scheme = verdicts[h]
            mismatches += accepted != ok
            needed += checked
            for scheme in in_ranges:
                in_ranges[scheme] += by_scheme[scheme]
    # every session also verified its trusted header's own commit: ONE
    # mixed commit, whose Edwards rows the measured cut-off routes
    ok1, checked1, _bad1, trusted = verdicts[1]
    needed += w.inits * checked1
    mismatches += 0 if ok1 else w.inits
    trusted_on_device = trusted[refm.ED25519] >= cb.MIN_TPU_BATCH
    want_device = in_ranges[refm.ED25519] + (
        w.inits * trusted[refm.ED25519] if trusted_on_device else 0)
    want_lane = in_ranges[refm.SECP256K1] + w.inits * trusted[refm.SECP256K1]

    stored_bad = 0
    for store in w.sessions:
        head = store.latest()
        if head is None or head.height != len(fx.chain.blocks):
            stored_bad += 1
        for h in fx.sample_heights:
            got = store.get(h)
            if got is None or got.encode() != fx.chain.blocks[h - 1].encode():
                stored_bad += 1

    routed = sum(v for k, v in d.items() if k.startswith("route.") and k.endswith(".sigs"))
    on_device = d.get("route.tpu.sigs", 0.0)
    by_ecdsa_route = ecdsa_routed(d)
    on_lane = sum(by_ecdsa_route.values())
    say(f"mixed: routed {routed:.0f} (the reference needs {needed}); route tpu "
        f"{on_device:.0f} (range calls need {in_ranges[refm.ED25519]} Edwards rows; a "
        f"trusted-header commit holds {trusted[refm.ED25519]}, cut-off {cb.MIN_TPU_BATCH}: "
        f"{'on' if trusted_on_device else 'off'} the device); ECDSA routes "
        f"{by_ecdsa_route} = {on_lane:.0f} (the reference needs {want_lane})")
    checks = [
        Check("verdict_mismatches", mismatches, 0),
        Check("stored_mismatches", stored_bad, 0),
        Check("sigs_verified_minus_needed", abs(routed - needed), 0),
        Check("edwards_sigs_on_device_minus_range_needed", abs(on_device - want_device), 0),
        Check("ecdsa_sigs_on_host_minus_needed", abs(on_lane - want_lane), 0),
    ]
    for lane, (height, index) in fx.warm_bad.items():
        ref_at = _ref_refusal_height(fx, height, index)
        got_at = fx.observed.get("warm_refused_at", {}).get(lane, -1)
        checks.append(Check(f"warmup_refusal_height_delta.{lane}",
                            abs(got_at - ref_at) + (0 if ref_at == height else 1), 0))
    checks.append(Check("headers_verified", w.units, 1, "min"))
    return checks + harness.device_served_checks(d), attempted, failed
