"""A validator set that mixes key types (ed25519 + secp256k1) through the
commit funnel's normal path: ONE collect walk, every row handed to the
range's one verifier whatever its key, the Edwards rows in one batch and
the secp256k1 rows on the verifier's host lane beside it.

Held against two oracles that share no code with that path: the plain
reference (`benchmark/reference_mixed`: OpenSSL, its own sign-bytes, the
low-S rule written out) and the per-entry `_verify_single` loop, a
`verify_one` a signature — what a mixed commit went through before. They
must agree on accept / refuse, on `failed_index`, on the index named, and
on the NUMBER of rows of each scheme that > 2/3 in index order needs: none
skipped, none verified twice.
"""

import dataclasses
import functools
from fractions import Fraction

import pytest

from benchmark import fixtures
from benchmark import reference_mixed as refm
from tendermint_tpu import testing as tt
from tendermint_tpu.crypto import backend_telemetry as bt
from tendermint_tpu.crypto import batch as cb
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.crypto.ed25519 import Ed25519PubKey
from tendermint_tpu.crypto.secp256k1 import HALF_N, N, Secp256k1PubKey
from tendermint_tpu.libs import trace
from tendermint_tpu.types import validation
from tendermint_tpu.types.validation import InvalidCommitError

CHAIN = "mixed-chain"
ED, EC = "ed25519", "secp256k1"
HEIGHTS = (5, 6, 7)  # the range's first, middle and last entry

#: name -> (validators, key types by creation index)
SETS = {
    "mixed20": (20, (ED, EC)),
    "mixed150": (150, (ED, EC)),
    "one_secp_in_20": (20, (EC,) + (ED,) * 19),
    "one_ed_in_20": (20, (ED,) + (EC,) * 19),
    "secp_first": (20, (ED, EC)),  # a seed whose first-sorted key is secp256k1
}


@functools.lru_cache(maxsize=None)
def _set(name: str):
    """(validator set, keys, the range's honest entries)."""
    n, key_types = SETS[name]
    tries = range(64) if name == "secp_first" else (0,)
    for k in tries:
        vals, keys = tt.make_validator_set(n, seed=f"{name}-{k}".encode(), key_types=key_types)
        if name != "secp_first" or vals.validators[0].pub_key.TYPE == EC:
            break
    assert name != "secp_first" or vals.validators[0].pub_key.TYPE == EC
    entries = []
    for h in HEIGHTS:
        bid = tt.make_block_id(b"%s-%d" % (name.encode(), h))
        entries.append((vals, bid, h, tt.make_commit(CHAIN, h, 0, bid, vals, keys)))
    return vals, keys, tuple(entries)


def _needed(vals) -> int:
    """Rows > 2/3 needs when everyone signs for the block (equal powers)."""
    return len(vals) * 2 // 3 + 1


def _pick(vals, scheme: str, where: str) -> int:
    """The validator index of a `scheme` row before / at / after the 2/3
    cut-off: the first such row inside the quorum, the last one inside it
    (the cut-off row itself where it is of this scheme), the first one
    beyond it. A set with ONE key of the scheme has it where it is."""
    q = _needed(vals)
    rows = [i for i, v in enumerate(vals.validators) if v.pub_key.TYPE == scheme]
    inside, beyond = [i for i in rows if i < q], [i for i in rows if i >= q]
    want = {"before": inside[:1], "at": inside[-1:], "after": beyond[:1]}[where]
    return (want or rows)[0]


def _flip(commit, index: int):
    return fixtures.corrupt_commit(commit, index)


def _with(entries, ei: int, commit):
    out = list(entries)
    vals, bid, h, _ = out[ei]
    out[ei] = (vals, bid, h, commit)
    return out


def _reference(entries):
    """(failed entry or None, index named or -1) from the plain reference."""
    failed, named = None, -1
    for ei, (vals, _bid, _h, commit) in enumerate(entries):
        ok, _n, bad, _by = refm.commit_verdict(fixtures.commit_data(CHAIN, commit, vals))
        if not ok and failed is None:
            failed, named = ei, bad
    return failed, named


def _rows_needed(entries) -> dict:
    """Rows by scheme a sound verifier collects for the range: per commit
    the for-block signatures in index order up to > 2/3, whatever their
    verdicts turn out to be."""
    by = {ED: 0, EC: 0}
    for vals, _bid, _h, commit in entries:
        need, tallied = vals.total_voting_power() * 2 // 3, 0
        for v, cs in zip(vals.validators, commit.signatures):
            if not cs.is_commit():
                continue
            by[v.pub_key.TYPE] += 1
            tallied += v.voting_power
            if tallied > need:
                break
    return by


def _single_oracle(entries, monkeypatch_ctx):
    """Per entry `_verify_single` (light semantics), a `verify_one` a row:
    (failed entry or None, the message, rows verified by scheme)."""
    calls = {ED: 0, EC: 0}
    real = vh.verify_one

    def counting(pk, msg, sig, lane="live"):
        calls[pk.TYPE] += 1
        return real(pk, msg, sig, lane=lane)

    monkeypatch_ctx.setattr(vh, "verify_one", counting)
    try:
        for ei, (vals, bid, h, commit) in enumerate(entries):
            try:
                validation._basic_commit_checks(vals, bid, h, commit)
                validation._verify_single(
                    CHAIN, vals, commit, vals.total_voting_power() * 2 // 3, False, True)
            except InvalidCommitError as e:
                return ei, str(e), calls
        return None, "", calls
    finally:
        monkeypatch_ctx.setattr(vh, "verify_one", real)


class _Counted:
    """verify_signature calls by scheme while the program runs (the host
    route of the suite verifies every row through them)."""

    def __init__(self, monkeypatch):
        self.calls = {ED: 0, EC: 0}
        for cls in (Ed25519PubKey, Secp256k1PubKey):
            real = cls.verify_signature

            def counting(pk, msg, sig, _real=real):
                self.calls[pk.TYPE] += 1
                return _real(pk, msg, sig)

            monkeypatch.setattr(cls, "verify_signature", counting)


@pytest.fixture
def recorder():
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.enabled = old
    trace.RECORDER.clear()


def _rows(recorder, key: str) -> list[dict]:
    return [s for s in recorder.dump() if f"{s['subsystem']}.{s['name']}" == key]


def _collected(recorder) -> dict:
    """Rows by lane the FIRST collect walk of the range handed over."""
    first = min(_rows(recorder, "validation.collect"), key=lambda s: s["start_s"])["attrs"]
    assert first["edwards"] + first["host"] == first["sigs"]
    return {ED: first["edwards"], EC: first["host"]}


@pytest.fixture
def process_hub():
    h = vh.acquire_hub(max_batch=512, window_ms=2.0, cache_size=0, adaptive=False)
    yield h
    vh.release_hub()


def _run_range(entries):
    """(failed entry or None, message) of verify_commit_range."""
    try:
        validation.verify_commit_range(CHAIN, entries)
    except InvalidCommitError as e:
        return e.failed_index, str(e)
    return None, ""


def _check_range(entries, monkeypatch, recorder, hub: bool):
    ref_failed, ref_named = _reference(entries)
    one_failed, one_msg, one_calls = _single_oracle(entries, monkeypatch)
    counted = _Counted(monkeypatch)
    routes_before = {k: v[1] for k, v in bt.ROUTES.items()}
    failed, msg = _run_range(entries)
    # accept / refuse and the entry named: all three agree
    assert failed == ref_failed == one_failed
    if failed is not None:
        # the index named, whichever lane found it
        assert msg == one_msg == f"invalid signature at index {ref_named}"
    # the first walk handed over exactly what > 2/3 in index order needs
    needed = _rows_needed(entries)
    assert _collected(recorder) == needed
    if failed is None:
        # none skipped, none verified twice (the hub verifies in its own
        # threads, through the same verify_signature)
        assert counted.calls == needed == one_calls
        if not hub:
            moved = {k: v[1] - routes_before.get(k, 0.0) for k, v in bt.ROUTES.items()}
            assert moved.get("host-ecdsa", 0) == needed[EC]
            assert moved.get("cpu", 0) == needed[ED] and not moved.get("cpu-fallback")
    via = {s["attrs"]["via"] for s in _rows(recorder, "validation.verify")}
    assert via == ({"hub"} if hub else {"local"})


# -- one flipped bit x lane x place in the quorum x place in the range ---------------


@pytest.mark.parametrize("entry", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("where", ["before", "at", "after"])
@pytest.mark.parametrize("scheme", [ED, EC])
@pytest.mark.parametrize("name", sorted(SETS))
def test_one_flipped_bit(name, scheme, where, entry, monkeypatch, recorder):
    vals, _keys, entries = _set(name)
    bad = _flip(entries[entry][3], _pick(vals, scheme, where))
    _check_range(_with(entries, entry, bad), monkeypatch, recorder, hub=False)


@pytest.mark.parametrize("entry", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("where", ["before", "at", "after"])
@pytest.mark.parametrize("scheme", [ED, EC])
def test_one_flipped_bit_with_a_running_hub(scheme, where, entry, monkeypatch, recorder,
                                            process_hub):
    vals, _keys, entries = _set("mixed20")
    bad = _flip(entries[entry][3], _pick(vals, scheme, where))
    _check_range(_with(entries, entry, bad), monkeypatch, recorder, hub=True)


@pytest.mark.parametrize("hub", [False, True], ids=["local", "hub"])
@pytest.mark.parametrize("name", sorted(SETS))
def test_honest_range(name, hub, monkeypatch, recorder, request):
    if hub:
        request.getfixturevalue("process_hub")
    _check_range(list(_set(name)[2]), monkeypatch, recorder, hub=hub)


def test_two_bad_commits_name_the_first_in_entry_order(monkeypatch, recorder):
    """An ECDSA row fails in the first entry and an Edwards row — at a LOWER
    index — in the last: the first entry is named, and within a commit
    that has both, the lower index."""
    vals, _keys, entries = _set("mixed20")
    lo, hi = sorted((_pick(vals, ED, "before"), _pick(vals, EC, "before")))
    rng = _with(entries, 0, _flip(entries[0][3], hi))
    rng = _with(rng, 2, _flip(entries[2][3], lo))
    failed, msg = _run_range(rng)
    assert (failed, msg) == (0, f"invalid signature at index {hi}")
    both = _flip(_flip(entries[1][3], hi), lo)
    failed, msg = _run_range(_with(entries, 1, both))
    assert (failed, msg) == (1, f"invalid signature at index {lo}")
    _check_range(_with(entries, 1, both), monkeypatch, recorder, hub=False)


# -- the low-S rule, nil and absent votes ----------------------------------------------


def _high_s(commit, index: int):
    cs = commit.signatures[index]
    r, s = cs.signature[:32], int.from_bytes(cs.signature[32:], "big")
    assert 0 < s <= HALF_N
    sigs = list(commit.signatures)
    sigs[index] = dataclasses.replace(cs, signature=r + (N - s).to_bytes(32, "big"))
    return dataclasses.replace(commit, signatures=tuple(sigs))


@pytest.mark.parametrize("where", ["before", "at", "after"])
def test_high_s_ecdsa_signature_is_refused_inside_the_quorum(where, monkeypatch, recorder):
    """(r, n - s) verifies under plain ECDSA; the reference's low-S rule
    refuses it, and so must the lane."""
    vals, _keys, entries = _set("mixed20")
    idx = _pick(vals, EC, where)
    bad = _high_s(entries[1][3], idx)
    rng = _with(entries, 1, bad)
    failed, msg = _run_range(rng)
    if where == "after":
        assert failed is None
    else:
        assert (failed, msg) == (1, f"invalid signature at index {idx}")
    _check_range(rng, monkeypatch, recorder, hub=False)


@pytest.mark.parametrize("case,nil,absent,accepted", [
    ("two-nil-one-absent", (1, 4), (2,), True),
    ("first-rows-absent", (), (0, 1, 2, 3), True),
    ("a-third-nil", tuple(range(0, 20, 3)), (), False),
    ("half-absent", (), tuple(range(0, 20, 2)), False),
])
def test_nil_and_absent_votes(case, nil, absent, accepted, monkeypatch, recorder):
    """Nil and absent votes move the cut-off: more rows are needed, of
    whichever schemes come next in index order — or > 2/3 is out of reach
    and the commit is refused before anything is verified."""
    vals, keys, entries = _set("mixed20")
    _v, bid, h, _c = entries[1]
    commit = tt.make_commit(CHAIN, h, 0, bid, vals, keys, nil_indices=frozenset(nil),
                            absent_indices=frozenset(absent))
    rng = _with(entries, 1, commit)
    failed, msg = _run_range(rng)
    ref_ok = refm.commit_verdict(fixtures.commit_data(CHAIN, commit, vals))[0]
    assert ref_ok is accepted
    if accepted:
        assert failed is None
        _check_range(rng, monkeypatch, recorder, hub=False)
    else:
        assert failed == 1 and msg.startswith(f"insufficient voting power at height {h}")
        # refused in the walk: nothing was verified, by either lane
        assert not _rows(recorder, "validation.verify")
        assert not _rows(recorder, "batch.host_lane")


# -- the single-commit funnel takes the same route ------------------------------------


def _single_entry_oracle(vals, commit, needed, count_all, by_index):
    try:
        validation._verify_single(CHAIN, vals, commit, needed, count_all, by_index)
    except InvalidCommitError as e:
        return str(e)
    return ""


@pytest.mark.parametrize("flip", [None, ED, EC], ids=["honest", "edwards-row", "ecdsa-row"])
@pytest.mark.parametrize("entry_point", ["verify_commit", "verify_commit_light",
                                         "verify_commit_light_trusting"])
@pytest.mark.parametrize("name", sorted(SETS))
def test_single_commit_entry_points(name, entry_point, flip, monkeypatch, recorder):
    vals, _keys, entries = _set(name)
    _v, bid, h, commit = entries[0]
    total = vals.total_voting_power()
    if entry_point == "verify_commit_light_trusting":
        needed, count_all, by_index, quorum = total // 3, False, False, Fraction(1, 3)
        where = "before"
    else:
        needed, count_all, by_index, quorum = total * 2 // 3, entry_point == "verify_commit", True, refm.QUORUM
        # verify_commit checks EVERY signature: one beyond the quorum fails it too
        where = "after" if count_all else "at"
    idx = -1
    if flip:
        idx = _pick(vals, flip, where)
        commit = _flip(commit, idx)
    want = _single_entry_oracle(vals, commit, needed, count_all, by_index)
    counted = _Counted(monkeypatch)
    try:
        if entry_point == "verify_commit_light_trusting":
            validation.verify_commit_light_trusting(CHAIN, vals, commit)
        else:
            getattr(validation, entry_point)(CHAIN, vals, bid, h, commit)
        got = ""
    except InvalidCommitError as e:
        got = str(e)
    assert got == want
    if not count_all:
        ok, checked, bad, by = refm.commit_verdict(
            fixtures.commit_data(CHAIN, commit, vals), quorum)
        assert (got == "") is ok
        if not ok:
            assert got == f"invalid signature at index {bad}"
        else:
            assert counted.calls == by and sum(by.values()) == checked
    elif flip:
        assert got == f"invalid signature at index {idx}"
    else:
        by = {ED: 0, EC: 0}
        for v in vals.validators:
            by[v.pub_key.TYPE] += 1
        assert counted.calls == by  # every signature, once


def test_a_mixed_commit_never_takes_the_per_signature_path(monkeypatch):
    vals, _keys, entries = _set("mixed20")
    monkeypatch.setattr(validation, "_verify_single",
                        lambda *a, **kw: pytest.fail("a verify_one a signature"))
    monkeypatch.setattr(vh, "verify_one", lambda *a, **kw: pytest.fail("verify_one"))
    validation.verify_commit_range(CHAIN, list(entries))
    _v, bid, h, commit = entries[0]
    validation.verify_commit_light(CHAIN, vals, bid, h, commit)
    validation.verify_commit(CHAIN, vals, bid, h, commit)
    validation.verify_commit_light_trusting(CHAIN, vals, commit)
    with pytest.raises(InvalidCommitError, match="invalid signature at index"):
        validation.verify_commit_range(
            CHAIN, _with(entries, 2, _flip(entries[2][3], _pick(vals, EC, "at"))))


# -- the lane runs beside the Edwards partition, and says so --------------------------


def test_host_lane_starts_before_the_edwards_route_and_is_joined_after_it(recorder):
    vals, _keys, entries = _set("mixed150")
    validation.verify_commit_range(CHAIN, list(entries))
    (route,) = _rows(recorder, "batch.route")
    (lane,) = _rows(recorder, "batch.host_lane")
    (wait,) = _rows(recorder, "batch.host_lane_wait")
    needed = _rows_needed(entries)
    assert route["attrs"]["n"] == needed[ED] and route["attrs"]["partitions"] == 2
    tasks = -(-needed[EC] // cb._HOST_LANE_TASK_ROWS)
    assert tasks > 1  # three commits' ECDSA rows: more than one pool task
    assert lane["attrs"] == {"n": needed[EC], "scheme": EC,
                             "workers": min(cb._POOL_WIDTH, tasks)}
    end = lambda s: s["start_s"] + s["duration_ms"] / 1e3  # noqa: E731
    assert lane["start_s"] <= route["start_s"] and end(route) <= end(lane) + 1e-6
    assert end(route) <= wait["start_s"] + 1e-6 and end(wait) <= end(lane) + 1e-6
    (collect,) = _rows(recorder, "validation.collect")
    assert collect["attrs"]["edwards"] == needed[ED] and collect["attrs"]["host"] == needed[EC]


def test_lane_verdicts_land_in_the_callers_order():
    vals, keys, entries = _set("mixed20")
    _v, bid, h, commit = entries[0]
    sb = commit.sign_bytes(CHAIN)
    items = [(v.pub_key, sb(i), cs.signature)
             for i, (v, cs) in enumerate(zip(vals.validators, commit.signatures))]
    want = [True] * len(items)
    for i in (_pick(vals, EC, "before"), _pick(vals, ED, "at"), _pick(vals, EC, "after")):
        items[i] = (items[i][0], b"another message", items[i][2])
        want[i] = False
    bv = cb.AdaptiveBatchVerifier()
    bv.add_many(items)
    assert bv.verify() == (False, want) and bv.last_route == "mixed"
    only = cb.AdaptiveBatchVerifier()
    only.add_many([it for it in items if it[0].TYPE == EC])
    assert only.verify() == (False, [w for w, it in zip(want, items) if it[0].TYPE == EC])
    assert only.last_route == "host-ecdsa"


@pytest.mark.parametrize("rows", [1, 63, 64, 65, 200])
def test_lane_cut_into_pool_tasks_keeps_every_row_and_its_place(rows, monkeypatch):
    """A lane of `rows` ECDSA rows goes to the pool in tasks of
    _HOST_LANE_TASK_ROWS: every row verified once, by its own task, and the
    verdicts (every seventh row spoilt) in the order the rows were added."""
    vals, keys, entries = _set("mixed150")
    _v, bid, h, commit = entries[0]
    sb = commit.sign_bytes(CHAIN)
    ec = [(v.pub_key, sb(i), cs.signature)
          for i, (v, cs) in enumerate(zip(vals.validators, commit.signatures))
          if v.pub_key.TYPE == EC]
    items = [ec[i % len(ec)] for i in range(rows)]
    want = [bool(i % 7) for i in range(rows)]
    items = [it if ok else (it[0], b"another message", it[2]) for it, ok in zip(items, want)]
    sliced = []
    orig = cb._verify_slice
    monkeypatch.setattr(cb, "_verify_slice", lambda part: sliced.append(len(part)) or orig(part))
    bv = cb.AdaptiveBatchVerifier()
    bv.add_many(items)
    assert bv.verify() == (all(want), want)
    step = cb._HOST_LANE_TASK_ROWS
    assert sorted(sliced, reverse=True) == [step] * (rows // step) + [rows % step] * bool(rows % step)


# -- (b) the all-Edwards path is what it was --------------------------------------------


@pytest.mark.parametrize("n", [20, 150])
def test_all_edwards_range_is_one_partition_and_no_lane(n, monkeypatch, recorder):
    vals, keys = tt.make_validator_set(n, seed=b"all-edwards-%d" % n)
    entries, want = [], []
    for h in HEIGHTS:
        bid = tt.make_block_id(b"ae-%d" % h)
        commit = tt.make_commit(CHAIN, h, 0, bid, vals, keys)
        entries.append((vals, bid, h, commit))
        sb = commit.sign_bytes(CHAIN)
        want += [(v.pub_key, sb(i), cs.signature) for i, (v, cs) in
                 enumerate(zip(vals.validators, commit.signatures))][:_needed(vals)]
    handed = []
    real = cb.AdaptiveBatchVerifier.add_many
    monkeypatch.setattr(cb.AdaptiveBatchVerifier, "add_many",
                        lambda self, items: (handed.append(list(items)), real(self, items))[1])
    validation.verify_commit_range(CHAIN, entries)
    # the same rows, in the same order, in ONE hand-over
    assert handed == [want]
    (route,) = _rows(recorder, "batch.route")
    assert route["attrs"]["partitions"] == 1 and route["attrs"]["n"] == len(want)
    assert not _rows(recorder, "batch.host_lane") and not _rows(recorder, "batch.host_lane_wait")
    (collect,) = _rows(recorder, "validation.collect")
    assert collect["attrs"]["edwards"] == len(want) and collect["attrs"]["host"] == 0


def test_all_secp256k1_set_goes_through_the_lane_alone(monkeypatch, recorder):
    vals, keys = tt.make_validator_set(8, seed=b"all-secp", key_types=(EC,))
    bid = tt.make_block_id(b"all-secp")
    commit = tt.make_commit(CHAIN, 3, 0, bid, vals, keys)
    validation.verify_commit_light(CHAIN, vals, bid, 3, commit)
    assert not _rows(recorder, "batch.route")
    (lane,) = _rows(recorder, "batch.host_lane")
    assert lane["attrs"]["n"] == 6 and lane["attrs"]["scheme"] == EC
    with pytest.raises(InvalidCommitError, match="invalid signature at index 2"):
        validation.verify_commit_light(CHAIN, vals, bid, 3, _flip(commit, 2))
