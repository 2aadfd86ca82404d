"""The light client's link step validates a validator set once a walk,
not once a header (ISSUE 30): `verify_adjacent_chain` carries the hash of
the last set it validated in full and `LightBlock.validate_basic` skips
`ValidatorSet.validate_basic` for a set that hashes to it.

What is held here: (a) the chain walk gives the verdict of a plain
header-by-header `verify_adjacent` loop — same head, or the same
exception type with the same message — on seeded chains (static set, one
rotation) crossed with seeded faults, the interesting ones being bad sets
whose hash the header AND the predecessor carry; (b) the `light.link`
span's `validated` counts the full validations; (c) a second set object
with the same keys and powers is pinned; (d) `ValidatorSet.validate_basic`
keeps its errors and their precedence; (e) the single-step paths carry no
pin.

(f) (ISSUE 34) a sequential session encodes a window's light blocks while
the window's signatures are on the device (`verify.while_in_flight`, here
over `tests/stub_dispatch.py`'s stub kernels): the trusted store holds the
same bytes with the mechanism engaged, partly engaged and on the host
route, and nothing of a walk that a bad commit or a diverging witness
ends, although bytes had been made ahead."""

import dataclasses
import random

import pytest
import stub_dispatch

from tendermint_tpu import testing as tt
from tendermint_tpu.crypto import backend_telemetry as bt
from tendermint_tpu.crypto.hashes import sha256
from tendermint_tpu.crypto.tpu import verify as tpu_verify
from tendermint_tpu.libs import trace
from tendermint_tpu.light import verifier
from tendermint_tpu.light.client import Divergence, LightClient, TrustedStore, TrustOptions
from tendermint_tpu.light.types import LightBlock, SignedHeader
from tendermint_tpu.light.verifier import VerificationError
from tendermint_tpu.types.block import BlockID, Header, PartSetHeader
from tendermint_tpu.types.validator_set import Validator, ValidatorSet

CHAIN_ID = "link-chain"
T0_NS = 1_700_000_000_000_000_000
PERIOD_NS = 10 * 365 * 24 * 3600 * 10**9
SEEDS = (3000003001, 3000003002, 3000003003)


def _raw_set(validators) -> ValidatorSet:
    """A set as a decode leaves it: the validators in the order given,
    nothing sorted, nothing checked, no memo."""
    vs = ValidatorSet.decode(b"")
    vs.validators = list(validators)
    return vs


def _fresh(vals: ValidatorSet) -> ValidatorSet:
    """Another object with the same validators, its hash not yet computed."""
    return ValidatorSet.decode(vals.encode())


def _make_set(n: int, tag: str, power: int = 10):
    return tt.make_validator_set(n, power=power, seed=tag.encode())


@dataclasses.dataclass
class Step:
    """What one height's light block carries. `signer` signs the commit;
    `vals` is the set attached to the block; `vh` / `next_vh` are what the
    header says."""

    vals: ValidatorSet
    signer: tuple
    vh: bytes
    next_vh: bytes


def _steps(n: int, plan) -> list[Step]:
    """Index h-1 = height h. `plan(h)` -> (vals, keys): the honest set."""
    out = []
    for h in range(1, n + 1):
        vals, keys = plan(h)
        out.append(Step(vals, (vals, keys), vals.hash(), plan(h + 1)[0].hash()))
    return out


def _forge(steps: list[Step]) -> list[LightBlock]:
    blocks, last_bid = [], BlockID()
    for h, st in enumerate(steps, start=1):
        hb = h.to_bytes(8, "big")
        signer_vals, keys = st.signer
        header = Header(
            chain_id=CHAIN_ID,
            height=h,
            time_ns=T0_NS + h * 1_000_000_000,
            last_block_id=last_bid,
            last_commit_hash=sha256(b"lc" + hb),
            data_hash=sha256(b"data" + hb),
            validators_hash=st.vh,
            next_validators_hash=st.next_vh,
            consensus_hash=sha256(b"consensus"),
            app_hash=sha256(b"app" + hb),
            last_results_hash=sha256(b"results"),
            evidence_hash=b"",
            proposer_address=signer_vals.validators[0].address,
        )
        bid = BlockID(header.hash(), PartSetHeader(1, sha256(b"p" + hb)))
        commit = tt.make_commit(
            CHAIN_ID, h, 0, bid, signer_vals, keys, timestamp_ns=header.time_ns
        )
        blocks.append(LightBlock(SignedHeader(header, commit), st.vals))
        last_bid = bid
    return blocks


def _now(blocks) -> int:
    return blocks[-1].header.time_ns + 1_000_000_000


# -- the plans and the faults ------------------------------------------------------

SET_A = _make_set(4, "link-a")
SET_B = _make_set(5, "link-b")
SET_C = _make_set(4, "link-c", power=7)


def _plan(kind: str, rng: random.Random, n: int):
    if kind == "static":
        return lambda h: SET_A
    rotate_at = rng.randint(2, n)
    return lambda h: SET_A if h < rotate_at else SET_B


def _carried(steps, f, bad):
    """Hang `bad` on height f with its hash in header f and in the
    predecessor's next_validators_hash: only validate_basic can refuse it."""
    steps[f - 1].vals = bad
    steps[f - 1].vh = bad.hash()
    steps[f - 2].next_vh = bad.hash()


def _fault_duplicate(steps, f):
    honest = steps[f - 1].vals.validators
    _carried(steps, f, _raw_set(honest + [honest[0]]))


def _fault_zero_power(steps, f):
    honest = steps[f - 1].vals.validators
    _carried(
        steps, f, _raw_set([honest[0], Validator(honest[1].pub_key, 0)] + honest[2:])
    )


def _fault_empty(steps, f):
    _carried(steps, f, _raw_set([]))


def _fault_set_not_headers(steps, f):
    # a sound set, but not the one header f names
    steps[f - 1].vals = ValidatorSet(steps[f - 1].vals.validators[:-1])


def _fault_not_promised(steps, f):
    # a sound set that header f names and signs with, but f-1 promised another
    steps[f - 1].vals = SET_C[0]
    steps[f - 1].signer = SET_C
    steps[f - 1].vh = SET_C[0].hash()


FAULTS = {
    "none": (None, None),
    "duplicate": (_fault_duplicate, (ValueError, "duplicate validator address")),
    "zero_power": (_fault_zero_power, (ValueError, "validator with non-positive power")),
    "empty": (_fault_empty, (ValueError, "empty validator set")),
    "set_not_headers": (
        _fault_set_not_headers,
        (ValueError, "validators hash does not match header"),
    ),
    "not_promised": (
        _fault_not_promised,
        (VerificationError, "untrusted validators hash != trusted next_validators_hash"),
    ),
}


def _outcome(fn):
    """What a call gave: what it returned, or the exception's type and
    message — the two things compared with the oracle's."""
    try:
        return ("returned", fn())
    except Exception as e:
        return (type(e), str(e))


def _plain_walk(trusted, chain, now):
    """The oracle: the reference's header-by-header VerifyAdjacent loop,
    every step with no pin — a full validate_basic per header."""
    prev = trusted
    for lb in chain:
        verifier.verify_adjacent(CHAIN_ID, prev, lb, PERIOD_NS, now)
        prev = lb
    return prev


@pytest.fixture
def full_validations(monkeypatch):
    """Counts the calls of ValidatorSet.validate_basic."""
    calls = []
    real = ValidatorSet.validate_basic

    def counting(self):
        calls.append(self)
        return real(self)

    monkeypatch.setattr(ValidatorSet, "validate_basic", counting)
    return calls


# -- (a) parity with the plain loop -----------------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("kind", ["static", "rotation"])
@pytest.mark.parametrize("fault", list(FAULTS))
def test_walk_matches_plain_loop(seed, kind, fault, full_validations):
    n = 12
    rng = random.Random(seed)
    steps = _steps(n, _plan(kind, rng, n))
    inject, expected = FAULTS[fault]
    f = rng.randint(2, n)
    if inject is not None:
        inject(steps, f)
    blocks = _forge(steps)
    trusted, chain, now = blocks[0], blocks[1:], _now(blocks)

    want = _outcome(lambda: _plain_walk(trusted, chain, now).height)
    plain_calls = len(full_validations)
    got = _outcome(
        lambda: verifier.verify_adjacent_chain(
            CHAIN_ID, trusted, chain, PERIOD_NS, now
        ).height
    )
    assert got == want
    # the fault is the one meant, and it is refused (or the head reached)
    assert want == (expected or ("returned", n))
    # the oracle validated a set a header as far as it got, the walk at most that
    assert plain_calls == (n - 1 if expected is None else f - 1)
    assert 1 <= len(full_validations) - plain_calls <= plain_calls


# -- (b) the counter --------------------------------------------------------------


@pytest.fixture
def recorder():
    """The process recorder, on and empty; restored afterwards."""
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.enabled = old
    trace.RECORDER.clear()


def _link_spans(recorder):
    return [s for s in recorder.dump(subsystem="light") if s["name"] == "link"]


def _cycling(h):
    return (SET_A, SET_B, SET_C)[h % 3]


@pytest.mark.parametrize(
    "kind,n,expected",
    [
        ("static", 129, 1),  # one 128-header window of one set
        ("rotation", 40, 2),
        ("cycling", 10, 9),  # every header another set than its predecessor's
    ],
)
def test_validated_counts_full_validations(kind, n, expected, recorder, full_validations):
    plan = _cycling if kind == "cycling" else _plan(kind, random.Random(SEEDS[0]), n - 1)
    blocks = _forge(_steps(n, plan))
    head = verifier.verify_adjacent_chain(
        CHAIN_ID, blocks[0], blocks[1:], PERIOD_NS, _now(blocks)
    )
    assert head is blocks[-1]
    (span,) = _link_spans(recorder)
    assert span["attrs"]["n"] == n - 1
    assert span["attrs"]["validated"] == expected
    assert len(full_validations) == expected


@pytest.mark.parametrize("windows", [2, 3])
def test_first_block_of_every_call_is_validated(windows, recorder, full_validations):
    """The pin is a local of one call: a second window over the same set
    starts without one, wherever its trusted block came from."""
    per = 5
    blocks = _forge(_steps(1 + windows * per, lambda h: SET_A))
    now, trusted = _now(blocks), blocks[0]
    for w in range(windows):
        trusted = verifier.verify_adjacent_chain(
            CHAIN_ID, trusted, blocks[1 + w * per : 1 + (w + 1) * per], PERIOD_NS, now
        )
    assert [s["attrs"]["validated"] for s in _link_spans(recorder)] == [1] * windows
    assert len(full_validations) == windows


# -- (c) the pin is the hash, not the object --------------------------------------


@pytest.mark.parametrize("seed", SEEDS)
def test_equal_set_in_another_object_is_pinned(seed, recorder, full_validations):
    """A real chain decodes a fresh ValidatorSet per header, each with the
    proposer priorities of its height: same keys and powers, same hash."""
    n = 8
    vals, keys = _make_set(4, f"link-{seed}")
    steps = _steps(n, lambda h: (vals, keys))
    for h, st in enumerate(steps, start=1):
        st.vals = _fresh(vals.copy_increment_proposer_priority(h))
    blocks = _forge(steps)
    sets = [lb.validators for lb in blocks]
    assert len({id(s) for s in sets}) == n
    assert len({s.encode() for s in sets}) > 1  # the priorities do differ
    head = verifier.verify_adjacent_chain(
        CHAIN_ID, blocks[0], blocks[1:], PERIOD_NS, _now(blocks)
    )
    assert head is blocks[-1]
    (span,) = _link_spans(recorder)
    assert span["attrs"]["validated"] == 1
    assert full_validations == [sets[1]]


# -- (d) ValidatorSet.validate_basic: same errors, same precedence ----------------


def _validate_basic_before(vs: ValidatorSet) -> None:
    """The body before ISSUE 30, letter for letter (address read twice)."""
    if not vs.validators:
        raise ValueError("empty validator set")
    seen = set()
    for v in vs.validators:
        if v.voting_power <= 0:
            raise ValueError("validator with non-positive power")
        if v.address in seen:
            raise ValueError("duplicate validator address")
        seen.add(v.address)


def _v(i: int, power: int = 10) -> Validator:
    return Validator(SET_B[0].validators[i].pub_key, power)


BAD_SETS = {
    "sound": ([_v(0), _v(1), _v(2)], None),
    "empty": ([], "empty validator set"),
    "zero_power": ([_v(0), _v(1, 0)], "validator with non-positive power"),
    "negative_power": ([_v(0, -3), _v(1)], "validator with non-positive power"),
    "duplicate": ([_v(0), _v(1), _v(0)], "duplicate validator address"),
    "duplicate_other_power": ([_v(0), _v(0, 3)], "duplicate validator address"),
    # the first offender wins
    "duplicate_then_zero": ([_v(0), _v(0), _v(1, 0)], "duplicate validator address"),
    "zero_then_duplicate": ([_v(0), _v(1, 0), _v(0)], "validator with non-positive power"),
    # one validator that is both: its power is looked at first
    "duplicate_with_zero_power": ([_v(0), _v(0, 0)], "validator with non-positive power"),
    "first_has_zero_power": ([_v(0, 0), _v(0)], "validator with non-positive power"),
}


@pytest.mark.parametrize("name", list(BAD_SETS))
def test_validator_set_validate_basic_table(name):
    validators, message = BAD_SETS[name]
    vs = _raw_set(validators)
    want = _outcome(lambda: _validate_basic_before(vs))
    assert _outcome(vs.validate_basic) == want
    assert want == (("returned", None) if message is None else (ValueError, message))


# -- (e) the single-step paths carry no pin ---------------------------------------


SINGLE_STEP = {
    # name -> (the call, the height of its untrusted block in a 4-block chain)
    "adjacent": (verifier.verify_adjacent, 2),
    "non_adjacent_on_neighbours": (verifier.verify_non_adjacent, 2),
    "non_adjacent": (verifier.verify_non_adjacent, 4),
    "dispatch_adjacent": (verifier.verify, 2),
    "dispatch_skipping": (verifier.verify, 4),
}


@pytest.mark.parametrize("call", list(SINGLE_STEP))
@pytest.mark.parametrize("fault", ["none", "duplicate", "zero_power"])
def test_single_step_validates_in_full(call, fault, full_validations):
    """A bad set whose hash header and predecessor both carry is refused
    on the single-step paths too; a sound one is validated at every call,
    also the second time the same set comes by."""
    step, target = SINGLE_STEP[call]
    steps = _steps(4, lambda h: SET_A)
    inject, expected = FAULTS[fault]
    if inject is not None:
        inject(steps, target)
    blocks = _forge(steps)

    def run():
        step(CHAIN_ID, blocks[0], blocks[target - 1], PERIOD_NS, _now(blocks))

    if expected is None:
        run()
        run()
        assert len(full_validations) == 2
    else:
        kind, message = expected
        with pytest.raises(kind, match=message):
            run()
        assert len(full_validations) == 1


# -- (f) the store's bytes, made ahead under a dispatch ------------------------------

SESSION_N = 300  # trusting height 1: windows of 128, 128 and 43 headers
WINDOWS = [128, 128, 43]
SESSION_BLOCKS = _forge(_steps(SESSION_N, lambda h: SET_A))
QUORUM = 3  # signatures of SET_A's four that pass 2/3


class _Memory:
    """A provider serving light blocks from a list (index height - 1)."""

    def __init__(self, blocks):
        self.blocks = blocks

    def chain_id(self):
        return CHAIN_ID

    async def light_block(self, height):
        return self.blocks[(height or len(self.blocks)) - 1]

    async def report_evidence(self, ev):
        pass


#: route -> the cut-off the device route starts at (None: the host serves
#: all); "partly" sits between the last window's signatures and a whole one's
ROUTES = {"engaged": 1, "partly": 64 * QUORUM, "host": None}


@pytest.fixture
def session(monkeypatch, recorder):
    """A sequential client over `blocks` on one of ROUTES; `saves` collects
    (height, bytes handed in or None) of every `TrustedStore.save`."""
    saves = []
    real = TrustedStore.save

    def save(self, lb, encoded=None):
        saves.append((lb.height, encoded))
        real(self, lb, encoded)

    monkeypatch.setattr(TrustedStore, "save", save)

    def make(route, blocks, witness=None):
        if ROUTES[route] is not None:
            # whole windows a chunk, so a window is one dispatch loop
            stub_dispatch.install_kernels(monkeypatch, [], max_bucket=8192)
            stub_dispatch.install_device_route(monkeypatch, cutoff=ROUTES[route])
        return LightClient(
            CHAIN_ID, TrustOptions(PERIOD_NS, 1, blocks[0].header.hash()),
            _Memory(blocks), [_Memory(witness or blocks)], sequential=True,
        )

    make.saves = saves
    yield make
    bt.reset()


def _stored(client) -> dict:
    return {int.from_bytes(k[-8:], "big"): v for k, v in client.store.db.iterate(b"lb/", b"lb0")}


def _light_spans(recorder, name):
    return [x["attrs"] for x in recorder.dump(subsystem="light") if x["name"] == name]


async def _run(client, height=SESSION_N):
    return await client.verify_light_block_at_height(height, _now(SESSION_BLOCKS))


@pytest.mark.asyncio
@pytest.mark.parametrize("route", list(ROUTES))
async def test_session_stores_the_same_bytes_on_every_route(route, session, recorder):
    client = session(route, SESSION_BLOCKS)
    head = await _run(client)
    assert head.height == SESSION_N
    want = {lb.height: lb.encode() for lb in SESSION_BLOCKS}
    assert _stored(client) == want
    # the anchor, then every block of the walk in order, then the head again
    assert [h for h, _raw in session.saves] == [1, *range(2, SESSION_N + 1), SESSION_N]
    ahead_windows = {"engaged": WINDOWS, "partly": WINDOWS[:2], "host": []}[route]
    encoded = _light_spans(recorder, "encode_ahead")
    assert [x["n"] for x in encoded] == ahead_windows
    first = 2
    for x in encoded:
        assert x["bytes"] == sum(len(want[h]) for h in range(first, first + x["n"]))
        first += x["n"]
    fills = [x["attrs"] for x in recorder.dump(subsystem="tpu") if x["name"] == "fill"]
    assert fills == [{"chunks": 1, "ran": True}] * len(ahead_windows)
    # bytes made ahead are the ones saved, and the rest is encoded at the store
    (store,) = _light_spans(recorder, "store")
    handed_in = [(h, raw) for h, raw in session.saves[1:] if raw is not None]
    assert all(raw == want[h] for h, raw in handed_in)
    at_store = len(session.saves) - 1 - len(handed_in)
    head_twice = 1 if route == "engaged" else 0
    assert store == {"n": SESSION_N, "ahead": sum(ahead_windows) + head_twice}
    assert store["ahead"] == len(handed_in) and store["ahead"] + at_store == store["n"]
    if route != "host":
        assert bt.BACKEND["fallbacks"] == 0 and "cpu-fallback" not in bt.ROUTES


def _with_refused_commit(blocks, height):
    """`blocks` with one signature of the quorum at `height` given an
    s >= L, which the host's prep (and so the stub's bitmap) refuses."""
    lb = blocks[height - 1]
    commit = lb.signed_header.commit
    sigs = list(commit.signatures)
    sigs[1] = dataclasses.replace(sigs[1], signature=sigs[1].signature[:32] + b"\xff" * 32)
    bad = dataclasses.replace(commit, signatures=tuple(sigs))
    out = list(blocks)
    out[height - 1] = LightBlock(SignedHeader(lb.header, bad), lb.validators)
    return out


@pytest.mark.asyncio
@pytest.mark.parametrize("route", ["engaged", "host"])
@pytest.mark.parametrize("height", [77, 200, 290])
async def test_a_refused_commit_leaves_nothing_of_the_walk(route, height, session, recorder):
    client = session(route, _with_refused_commit(SESSION_BLOCKS, height))
    with pytest.raises(VerificationError, match=f"invalid commit at height {height}:"):
        await _run(client)
    assert _stored(client) == {1: SESSION_BLOCKS[0].encode()}
    assert [h for h, _raw in session.saves] == [1]
    assert _light_spans(recorder, "store") == []
    # every window up to the refused one was encoded ahead, and dropped
    windows = WINDOWS[: 1 + (height > 129) + (height > 257)]
    encoded = [x["n"] for x in _light_spans(recorder, "encode_ahead")]
    assert encoded == (windows if route == "engaged" else [])
    assert getattr(tpu_verify._dispatch_local, "work", None) is None


@pytest.mark.asyncio
@pytest.mark.parametrize("route", ["engaged", "host"])
async def test_a_diverging_witness_leaves_only_the_anchor(route, session, recorder):
    """The witness serves another, validly signed header at the target
    height: Divergence, after every window's bytes were made ahead, and
    none of them in the store."""
    steps = _steps(SESSION_N, lambda h: SET_A)
    lb = SESSION_BLOCKS[-1]
    evil = dataclasses.replace(lb.header, app_hash=sha256(b"evil"))
    bid = BlockID(evil.hash(), lb.signed_header.commit.block_id.part_set_header)
    commit = tt.make_commit(CHAIN_ID, SESSION_N, 0, bid, *steps[-1].signer,
                            timestamp_ns=evil.time_ns)
    fork = SESSION_BLOCKS[:-1] + [LightBlock(SignedHeader(evil, commit), lb.validators)]
    client = session(route, SESSION_BLOCKS, witness=fork)
    with pytest.raises(Divergence):
        await _run(client)
    assert _stored(client) == {1: SESSION_BLOCKS[0].encode()}
    assert [h for h, _raw in session.saves] == [1]
    encoded = [x["n"] for x in _light_spans(recorder, "encode_ahead")]
    assert encoded == (WINDOWS if route == "engaged" else [])
    assert _light_spans(recorder, "store") == []


@pytest.mark.asyncio
@pytest.mark.parametrize("strategy", ["skipping", "backwards"])
async def test_other_strategies_encode_at_the_store(strategy, session, recorder):
    client = session("engaged", SESSION_BLOCKS)
    if strategy == "skipping":
        client.sequential = False
        await _run(client, 40)
    else:
        client.trust_options = TrustOptions(PERIOD_NS, 40, SESSION_BLOCKS[39].header.hash())
        await _run(client, 30)
    assert _light_spans(recorder, "encode_ahead") == []
    (store,) = _light_spans(recorder, "store")
    assert store["ahead"] == 0 and store["n"] >= 2
    assert all(raw is None for _h, raw in session.saves)
    stored = _stored(client)
    assert all(stored[h] == SESSION_BLOCKS[h - 1].encode() for h in stored)
