"""Driver `light_sequential`: stand-alone light clients catching up header
by header.

The entry the window drives is `light.LightClient.verify_light_block_at_height`
in sequential mode (its own 128-header windows, FETCH_CONCURRENCY 16, a
MemDB trusted store, one primary and one witness stand-in serving the same
chain from memory) with NO VerifyHub: the light client acquires none, so
each 128-header window reaches the AdaptiveBatchVerifier whole and goes out
as 8192-row chunks. No hub means no verdict cache: every session repeats
all of the work.

Closed loop, one client: fresh clients one after another, each trusting
height 1 and verifying to the chain's head. The window closes at the first
completed 128-header window at or after --seconds; the rate is over all
headers verified and all the time that passed.
"""

from __future__ import annotations

import asyncio
import time
from dataclasses import dataclass, field

from benchmark import fixtures, harness
from benchmark import reference as ref
from benchmark.harness import Check, say

END_TO_END = "light_headers_per_s"


class WindowClosed(Exception):
    """Raised out of the client's verify call once the window's time is up."""


@dataclass
class Fixture:
    chain: fixtures.LightChain
    warm: fixtures.LightChain
    warm_bad_height: int
    warm_bad_index: int
    sample_heights: list
    observed: dict = field(default_factory=dict)


def build(cfg: dict, cell: dict, seed: int):
    """Yields the whole fixture ONCE (`harness.assemble`); built in the
    measured process (`harness.FixtureHere`): the window walks these very
    objects, the one `ValidatorSet` under every `LightBlock` of a chain."""
    p, v = cell["traffic"], cfg["validators"]
    chain = fixtures.light_chain(seed, "light", p["headers"], v["count"], v["power"])
    warm = fixtures.light_chain(seed, "lwarm", p["warmup_headers"], v["count"], v["power"])
    needed = ref.commit_verdict(warm.commit_data(1))[1]  # signatures to > 2/3
    fx = Fixture(
        chain=chain,
        warm=warm,
        # the corrupted signature sits among the LAST few a verifier must
        # check, so a verifier that stops short of > 2/3 lets it through
        warm_bad_height=fixtures.seeded_index(seed, "lbadh", 2, min(17, p["warmup_headers"])),
        warm_bad_index=fixtures.seeded_index(seed, "lbadi", needed - max(1, needed // 10), needed - 1),
        sample_heights=sorted(
            {fixtures.seeded_index(seed, f"ls{i}", 2, p["headers"]) for i in range(32)}
            | {1, p["headers"]}
        ),
    )
    say(f"light: built {p['headers']}-header chain + {p['warmup_headers']}-header "
        f"warm-up chain, {v['count']} validators ({needed} signatures reach > 2/3); "
        f"warm-up corruption at height {fx.warm_bad_height}, signature {fx.warm_bad_index}")
    yield fx


class _MemoryProvider:
    """A light-block provider stand-in serving one chain from memory with
    zero link delay (the configuration states it)."""

    def __init__(self, chain_id: str, blocks: list):
        self._chain_id = chain_id
        self.blocks = blocks
        self.fetches = 0

    def chain_id(self) -> str:
        return self._chain_id

    async def light_block(self, height: int):
        from tendermint_tpu.light.provider import LightBlockNotFoundError

        self.fetches += 1
        h = height or len(self.blocks)
        if not 1 <= h <= len(self.blocks):
            raise LightBlockNotFoundError(str(h))
        return self.blocks[h - 1]

    async def report_evidence(self, ev) -> None:
        pass


def _client(chain: fixtures.LightChain, blocks: list):
    from tendermint_tpu.light.client import LightClient, TrustOptions

    return LightClient(
        chain.chain_id,
        TrustOptions(chain.period_ns, 1, blocks[0].header.hash()),
        _MemoryProvider(chain.chain_id, blocks),
        [_MemoryProvider(chain.chain_id, blocks)],
        sequential=True,
    )


def _refused_height(err: Exception, default: int) -> int:
    """`invalid commit at height 7: ...` -> 7 (verify_adjacent_chain names
    the offending height)."""
    msg = str(err)
    return int(msg.split("at height ")[1].split(":")[0]) if "at height " in msg else default


def _assert_no_hub() -> None:
    from tendermint_tpu.crypto.verify_hub import running_hub

    if running_hub() is not None:
        raise RuntimeError("a VerifyHub is running: the light client acquires none")


def install(patches: harness.Patches, spans: harness.Spans, traced: bool) -> None:
    """The spans this driver reads: `verify` around verify_commit_range as
    verify_adjacent_chain calls it; in a traced run the host's share of
    each dispatch too."""
    from tendermint_tpu.light import verifier

    patches.span(verifier, "verify_commit_range", spans, "verify")
    if traced:
        harness.host_prep_spans(patches, spans)


def warmup(fx: Fixture, cfg: dict, cell: dict, spans: harness.Spans) -> list[str]:
    """One clean session over the warm-up chain (other chain ID and keys):
    its one 128-header window is > 8192 signatures, the window's one
    dispatch shape. Then the same chain with one corrupted commit, which
    the client must refuse at exactly that height."""
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.light.verifier import VerificationError

    _assert_no_hub()
    n = len(fx.warm.blocks)
    t0 = time.perf_counter()
    asyncio.run(_client(fx.warm, fx.warm.blocks).verify_light_block_at_height(n, fx.warm.now_ns))
    t1 = time.perf_counter()
    bad = fixtures.with_corrupt_header(fx.warm, fx.warm_bad_height, fx.warm_bad_index)
    refused_at = -1
    try:
        asyncio.run(_client(fx.warm, bad).verify_light_block_at_height(n, fx.warm.now_ns))
    except VerificationError as e:
        refused_at = _refused_height(e, -1)
        say(f"light warm-up: corrupted commit refused: {str(e)[:120]}")
    fx.observed["warm_refused_at"] = refused_at
    keys = ref.commit_verdict(fx.warm.commit_data(1))[1]
    sigs = (n - 1) * keys
    shapes = [f"eq 8192/gb127 x{-(-sigs // 8192)} ({sigs} signatures, {keys} keys)",
              "per-signature 8192 (the refusal's attribution)"]
    if keys >= cb.MIN_TPU_BATCH:
        shapes.append(f"eq 128/gb127 ({keys}-signature single commits: cut-off "
                      f"{cb.MIN_TPU_BATCH} reaches them)")
    say(f"light warm-up: clean session {t1 - t0:.1f}s, refusal session "
        f"{time.perf_counter() - t1:.1f}s; warmed {shapes}")
    return shapes


@dataclass
class Window:
    elapsed: float = 0.0
    t0: float = 0.0
    t1: float = 0.0
    units: int = 0  # headers verified by completed 128-header windows
    sessions: list = field(default_factory=list)  # completed sessions' stores
    calls: list = field(default_factory=list)  # (session, first, last, refused_at)
    inits: int = 0  # sessions whose trusted header was verified
    trace: harness.DeviceTrace | None = None

    @property
    def metrics(self) -> dict:
        return {END_TO_END: self.units / self.elapsed}


def window(fx: Fixture, cfg: dict, cell: dict, seconds: float,
           patches: harness.Patches, trace: harness.DeviceTrace | None,
           spans: harness.Spans, on_close=lambda: None) -> Window:
    """The window; `on_close()` is called at the instant it closes. In a
    traced run the same traffic then goes on for `trace_seconds` under the
    profiler (stopping a trace stalls the host for seconds, so the traced
    stretch FOLLOWS the window whose spans and counters are read)."""
    from tendermint_tpu.light import verifier
    from tendermint_tpu.light.verifier import VerificationError

    _assert_no_hub()
    w = Window(trace=trace)
    n = len(fx.chain.blocks)
    state = {"session": 0, "deadline": 0.0, "closed": False, "stretch_end": 0.0}
    trace_seconds = float(cell["traffic"].get("trace_seconds", 3))

    def tick(now: float) -> None:
        """Close the window when its time is up; end the run when the
        traced stretch (if any) is over too."""
        if not state["closed"]:
            if now < state["deadline"]:
                return
            w.t1 = now
            state["closed"] = True
            on_close()
            if trace is None:
                raise WindowClosed()
            trace.start()
            state["stretch_end"] = time.perf_counter() + trace_seconds
        elif now >= state["stretch_end"]:
            raise WindowClosed()

    def make(orig):
        def wrapped(chain_id, trusted, chain, *a, **kw):
            try:
                out = orig(chain_id, trusted, chain, *a, **kw)
            except VerificationError as e:
                refused_at = _refused_height(e, chain[0].height)
                w.calls.append((state["session"], chain[0].height, chain[-1].height, refused_at))
                raise
            w.calls.append((state["session"], chain[0].height, chain[-1].height, 0))
            if not state["closed"]:
                w.units += len(chain)
            tick(time.perf_counter())
            return out

        return wrapped

    patches.wrap(verifier, "verify_adjacent_chain", make)

    async def run():
        w.t0 = time.perf_counter()
        state["deadline"] = w.t0 + seconds
        while True:
            client = _client(fx.chain, fx.chain.blocks)
            w.inits += 1
            try:
                await client.verify_light_block_at_height(n, fx.chain.now_ns)
                w.sessions.append(client.store)
                state["session"] += 1
                tick(time.perf_counter())
            except WindowClosed:
                return
            except VerificationError as e:
                say(f"light window: session {state['session']} REFUSED: {e}")
                if not state["closed"]:
                    w.t1 = time.perf_counter()
                    on_close()
                return

    asyncio.run(run())
    if trace is not None:
        trace.stop()
    w.elapsed = w.t1 - w.t0
    say(f"light window: {w.units} headers in {w.elapsed:.3f}s; {len(w.calls)} 128-header "
        f"windows and {len(w.sessions)} whole sessions in all"
        + (f" (with the {trace_seconds:g}s traced stretch that followed)" if trace else ""))
    return w


def compare(fx: Fixture, w: Window, d: dict, spans: harness.Spans) -> tuple[list[Check], int, int]:
    """The timed path's own products against the plain reference: the
    verdict on every commit the window consumed, what each whole session
    stored, the signatures the program verified against the signatures
    the > 2/3 rule needs, the warm-up's refusal — and that the device,
    not a host re-verify, served the window. Returns (checks, attempted,
    failed)."""
    heights = sorted({h for _s, a, b, _r in w.calls for h in range(a, b + 1)} | {1})
    verdicts = dict(zip(heights, ref.commit_verdicts(
        [fx.chain.commit_data(h) for h in heights])))
    mismatches = attempted = failed = needed = 0
    for _s, a, b, refused_at in w.calls:
        for h in range(a, b + 1):
            if refused_at and h > refused_at:
                break
            attempted += 1
            accepted = not (refused_at and h == refused_at)
            failed += not accepted
            mismatches += accepted != verdicts[h][0]
            needed += verdicts[h][1]
    # every session also verified its trusted header's own commit
    needed += w.inits * verdicts[1][1]
    mismatches += 0 if verdicts[1][0] else w.inits

    stored_bad = 0
    for store in w.sessions:
        head = store.latest()
        if head is None or head.height != len(fx.chain.blocks):
            stored_bad += 1
        for h in fx.sample_heights:
            got = store.get(h)
            if got is None or got.encode() != fx.chain.blocks[h - 1].encode():
                stored_bad += 1

    warm_v = ref.commit_verdicts(
        [fixtures.commit_data(fx.warm.chain_id, lb.signed_header.commit, lb.validators)
         for lb in fixtures.with_corrupt_header(fx.warm, fx.warm_bad_height,
                                                fx.warm_bad_index)[1:fx.warm_bad_height]])
    ref_refuses_at = next((i + 2 for i, v in enumerate(warm_v) if not v[0]), -1)
    routed = sum(v for k, v in d.items() if k.startswith("route.") and k.endswith(".sigs"))
    checks = [
        Check("verdict_mismatches", mismatches, 0),
        Check("stored_mismatches", stored_bad, 0),
        Check("sigs_verified_minus_needed", abs(routed - needed), 0),
        Check("warmup_refusal_height_delta",
              abs(fx.observed.get("warm_refused_at", -1) - ref_refuses_at)
              + (0 if ref_refuses_at == fx.warm_bad_height else 1), 0),
        Check("headers_verified", w.units, 1, "min"),
    ] + harness.device_served_checks(d)
    return checks, attempted, failed


def release(fx: Fixture) -> None:
    """Nothing to give back: this configuration acquires no hub."""
