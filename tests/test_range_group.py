"""The commit funnel with a hub running: a block-sync range is ONE group in
the hub (`VerifyHub.verify_many`) and leaves it as ONE dispatch — whatever
`max_batch` says about lone requests — and the funnel's error surface is what
it was: the offending commit is named, a plan over two validator sets
verifies each commit under its own."""

import pytest

from tendermint_tpu import testing as tt
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.types import validation
from tendermint_tpu.types.block import CommitSig
from tendermint_tpu.types.validator_set import Validator

CHAIN = "range-group"
N_VALS = 16
#: light semantics stop at > 2/3 of the power: 11 of 16 equal validators
QUORUM = 11
COMMITS = 64


@pytest.fixture
def hub():
    h = vh.acquire_hub(max_batch=32, window_ms=1.0, cache_size=4096, adaptive=False)
    yield h
    vh.release_hub()


def _range(sets, first=1):
    """One entry a set of `sets`, heights from `first`: (vals, block id, height, commit)."""
    entries = []
    for k, (vals, keys) in enumerate(sets):
        bid = tt.make_block_id(b"range-%d" % (first + k))
        commit = tt.make_commit(CHAIN, first + k, 0, bid, vals, keys)
        entries.append((vals, bid, first + k, commit))
    return entries


def _corrupt(entries, k, idx):
    vals, bid, height, commit = entries[k]
    sigs = list(commit.signatures)
    cs = sigs[idx]
    bad = bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
    sigs[idx] = CommitSig.for_block(cs.validator_address, cs.timestamp_ns, bad)
    entries[k] = (vals, bid, height, type(commit)(height, commit.round, bid, tuple(sigs)))


def test_a_64_commit_range_is_one_hub_dispatch(hub):
    one = tt.make_validator_set(N_VALS)
    entries = _range([one] * COMMITS)
    s0 = hub.stats()
    validation.verify_commit_range(CHAIN, entries, lane="backfill")
    s1 = hub.stats()
    assert s1["dispatches"] - s0["dispatches"] == 1
    assert s1["dispatched_sigs"] - s0["dispatched_sigs"] == COMMITS * QUORUM
    assert s1["bulk_groups"] - s0["bulk_groups"] == 1
    assert s1["lane_backfill_dispatched"] == COMMITS * QUORUM and s1["verify_errors"] == 0
    # the same range again (a peer re-sent it): the LRU answers, nothing dispatches
    validation.verify_commit_range(CHAIN, entries, lane="backfill")
    s2 = hub.stats()
    assert s2["dispatches"] == s1["dispatches"]
    assert s2["cache_hits"] - s1["cache_hits"] == COMMITS * QUORUM


@pytest.mark.parametrize("k", [0, 37, COMMITS - 1])
def test_a_corrupted_signature_still_names_its_commit(hub, k):
    one = tt.make_validator_set(N_VALS)
    entries = _range([one] * COMMITS)
    _corrupt(entries, k, idx=QUORUM - 2)
    with pytest.raises(validation.InvalidCommitError, match="invalid signature at index 9") as e:
        validation.verify_commit_range(CHAIN, entries, lane="backfill")
    assert e.value.failed_index == k
    # the range went out once, whole; locating re-asks commit by commit,
    # and every sound row is answered from the LRU
    s = hub.stats()
    assert s["bulk_group_sigs"] >= COMMITS * QUORUM and s["verify_errors"] == 0
    assert s["submitted"] == COMMITS * QUORUM
    assert s["cache_hits"] == (k + 1) * QUORUM


def test_a_plan_over_two_sets_verifies_each_commit_under_its_own(hub):
    """What the churn planner hands over: one call, two validator sets (a
    power change re-orders the committee from one height on)."""
    vals, keys = tt.make_validator_set(N_VALS)
    moved = vals.copy()
    mover = moved.validators[-1]
    moved.update_with_change_set([Validator(mover.pub_key, mover.voting_power + 1)])
    assert moved.validators[0].address == mover.address  # now sits first
    sets = [(vals, keys)] * 20 + [(moved, keys)] * 12
    entries = _range(sets)
    s0 = hub.stats()
    validation.verify_commit_range(CHAIN, entries, lane="backfill")
    s1 = hub.stats()
    assert s1["dispatches"] - s0["dispatches"] == 1
    # 11 of 16 at power 10; with the mover at 11 the first 11 carry 111 of 161: still 11
    assert s1["dispatched_sigs"] - s0["dispatched_sigs"] == 32 * QUORUM
    # a commit of the NEW set held to the OLD one is position-indexed nonsense: refused there
    stale = list(entries)
    stale[25] = (vals, *stale[25][1:])
    with pytest.raises(validation.InvalidCommitError) as e:
        validation.verify_commit_range(CHAIN, stale, lane="backfill")
    assert e.value.failed_index == 25
