"""The changing committee's own files at a tiny size on the CPU: the plain
reference (`reference_churn`) against the specification as the program
encodes it, the `blocksync_churn` driver on the host route (every device
check reads false by design, every other number compared holds), the control,
and the `churn_readers` arithmetic on readings made by hand — with a program
that records the planner's spans and with one that does not (the parent of
the PR that added them): nothing to read, never a raise."""

import asyncio
from types import SimpleNamespace

import pytest

from benchmark import churn_readers as cr
from benchmark import control, control_churn, fixtures_churn, run
from benchmark import program_spans as ps
from benchmark import reference as ref
from benchmark import reference_churn as refc
from benchmark.tests import tiny_churn

HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs"}


# -- the reference ---------------------------------------------------------------------


def _key(i):
    return bytes([i]) * 32


def test_a_change_in_block_h_is_the_set_of_h_plus_2():
    genesis = [(_key(i), 10) for i in range(1, 5)]
    txs = {3: (b"k=v", b"val:" + _key(2).hex().encode() + b"!11"),
           5: (b"val:" + _key(4).hex().encode() + b"!0", b"val:" + _key(9).hex().encode() + b"!10")}
    sets = refc.derive_sets(genesis, txs, 8)
    assert len(sets) == 11 and sets[0] is None
    assert sets[1] == sets[4] and sets[5] != sets[4] and sets[5] == sets[6] and sets[7] == sets[10]
    # order: power descending, then address; the mover sits first
    assert sets[5].pubkeys[0] == _key(2) and sets[5].powers == (11, 10, 10, 10)
    rest = sorted((_key(i) for i in (1, 3, 4)), key=refc.address)
    assert list(sets[5].pubkeys[1:]) == rest
    assert set(sets[7].pubkeys) == {_key(1), _key(2), _key(3), _key(9)}
    assert len({s.hash for s in sets[1:]}) == 3
    # the control's derivation: every height one set behind
    stale = refc.one_height_stale(sets)
    assert stale[1] == sets[1] and all(stale[h] == sets[h - 1] for h in range(2, 11))


def test_plans_end_at_a_third_set_and_a_set_that_returns_is_known():
    genesis = [(_key(i), 10) for i in range(1, 5)]
    up, down = (b"val:" + _key(2).hex().encode() + b"!11",), (b"val:" + _key(2).hex().encode() + b"!10",)
    sets = refc.derive_sets(genesis, {4: up, 8: up[:0], 9: (b"val:" + _key(3).hex().encode() + b"!11",)}, 20)
    # sets: A (1-5), B (6-10), C (11-)
    assert refc.expected_plans(sets, 1, 16) == [(1, 5), (6, 5), (11, 6)]
    assert refc.expected_plans(sets, 5, 8) == [(5, 6), (11, 2)]  # A and B are both known at 5
    assert refc.plan_end(sets, 1, 3) == 3  # the run ends first
    back = refc.derive_sets(genesis, {4: up, 6: down}, 20)  # A (1-5), B (6-7), A again (8-)
    assert back[8].hash == back[1].hash
    assert refc.expected_plans(back, 1, 16) == [(1, 5), (6, 2), (8, 9)]
    assert refc.expected_plans(back, 5, 12) == [(5, 12)]  # at 5 the state holds A and B
    static = refc.derive_sets(genesis, {}, 20)
    assert refc.expected_plans(static, 1, 20) == [(1, 20)]


def test_the_reference_s_sets_and_hashes_are_the_program_s():
    chain = asyncio.run(fixtures_churn.churn_chain(11, "cref", 40, 7, 10, 2, 4, 4))
    assert sorted(chain.changes) == list(range(4, 41, 4))
    assert [chain.changes[h] for h in (4, 8, 12, 16)] == ["power", "power", "power", "swap"]
    for h in range(1, 41):
        assert chain.sets[h].hash == chain.set_hash_at[h]
        prog = chain.set_objs[chain.set_hash_at[h]]
        assert tuple(v.pub_key.bytes() for v in prog.validators) == chain.sets[h].pubkeys
        assert tuple(v.voting_power for v in prog.validators) == chain.sets[h].powers
        assert ref.commit_verdict(chain.commit_data(h))[0] is True
    assert len(chain.txs_at[16]) == 4 and len(chain.txs_at[8]) == 3  # a swap is two val: txs
    assert refc.kv_state_hash([tx for h in range(1, 41) for tx in chain.txs_at[h]]) \
        == chain.app_hash_at[40]
    # a val: transaction hashed as a key is another state
    assert ref.kv_state_hash([tx for h in range(1, 41) for tx in chain.txs_at[h]]) \
        != chain.app_hash_at[40]
    # the same seed gives the same chain; another seed another committee
    again = asyncio.run(fixtures_churn.churn_chain(11, "cref", 40, 7, 10, 2, 4, 4))
    assert [s.hash for s in again.sets[1:]] == [s.hash for s in chain.sets[1:]]
    other = asyncio.run(fixtures_churn.churn_chain(12, "cref", 40, 7, 10, 2, 4, 4))
    assert other.sets[1].hash != chain.sets[1].hash and sorted(other.changes) == sorted(chain.changes)


# -- the driver, on the host route -----------------------------------------------------


def _failed(res):
    return {k for k, c in res["checks"].items() if not c["ok"]}


def test_host_route_run_holds_every_other_check(tmp_path):
    res = run.execute(tiny_churn.make_root(str(tmp_path)), tiny_churn.CELL, 3000003521, 0.3,
                      False, device=tiny_churn.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS and res["correct"] is False
    assert res["metrics"]["blocksync_blocks_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0 and res["failed"] == 0


def test_control_stale_set_is_not_correct(tmp_path):
    assert control.CONTROLS["stale_set"] is control_churn.stale_set
    with control_churn.stale_set():
        res = run.execute(tiny_churn.make_root(str(tmp_path)), tiny_churn.CELL, 3000003523, 0.3,
                          False, device=tiny_churn.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS | {"warmup_refusal_height_delta.stale_set"}


# -- the readers -----------------------------------------------------------------------


def _recorded(monkeypatch, rows, units=128):
    def window_rows(t0, t1):
        return [d for d in rows if d["end"] > t0 and d["start"] < t1]

    monkeypatch.setattr(ps, "window_rows", window_rows)
    return SimpleNamespace(t0=10.0, t1=20.0, stretch=(20.0, 25.0), device_kind="TPU v5 lite",
                           trace=None, units=units, counters={})


def _row(key, start, end, **attrs):
    sub, name = key.split(".", 1)
    return {"subsystem": sub, "name": name, "start": start, "end": end, "attrs": attrs}


def test_planner_readers_on_a_window_made_by_hand(monkeypatch):
    rows = [
        _row("blocksync.range", 10.0, 13.0, first=1, n=64),
        _row("blocksync.plan", 10.1, 10.1, run=64, planned=17, sets=1, cut="third_set"),
        _row("blocksync.plan", 11.0, 11.0, run=47, planned=16, sets=1, cut="third_set"),
        _row("blocksync.plan", 12.0, 12.0, run=31, planned=31, sets=2, cut="run_end"),
        _row("blocksync.range", 13.0, 16.0, first=65, n=64),
        _row("blocksync.plan", 13.1, 13.1, run=64, planned=64, sets=1, cut="run_end"),
        _row("blocksync.plan", 16.5, 16.5, run=3, planned=0, sets=0, cut="third_set"),  # no call
        _row("blocksync.sequential", 16.5, 16.9, n=1, applied=0),
        _row("state.valset_update", 12.5, 12.6, changes=1, size=150),
        _row("state.valset_update", 15.5, 15.7, changes=2, size=150),
        _row("blocksync.plan", 21.0, 21.0, run=64, planned=9, sets=1, cut="third_set"),  # after it
    ]
    r = _recorded(monkeypatch, rows)
    assert cr.plan_commits_per_verify(r) == pytest.approx((17 + 16 + 31 + 64) / 4)
    assert cr.plan_sets_per_verify(r) == 2.0
    assert cr.cuts_per_range(r) == pytest.approx(2 / 2)
    assert cr.sequential_block_share(r) == 0.0
    assert cr.ms_per_unit(r, "state.valset_update") == pytest.approx(1e3 * 0.3 / 128)
    rows.append(_row("blocksync.sequential", 17.0, 18.0, n=16, applied=16))
    assert cr.sequential_block_share(_recorded(monkeypatch, rows)) == pytest.approx(100 * 16 / 128)


def test_planner_readers_find_nothing_on_the_parent(monkeypatch):
    """The parent's spans: ranges and verifies, no plan, no sequential, no
    valset_update row."""
    rows = [_row("blocksync.range", 10.0, 13.0, first=1, n=64),
            _row("blocksync.verify", 10.1, 11.0, sigs=6464)]
    r = _recorded(monkeypatch, rows)
    assert cr.plan_commits_per_verify(r) is None and cr.plan_sets_per_verify(r) is None
    assert cr.cuts_per_range(r) is None and cr.sequential_block_share(r) is None
    assert cr.ms_per_unit(r, "state.valset_update") is None
    r = _recorded(monkeypatch, [])
    assert cr.plan_commits_per_verify(r) is None and cr.sequential_block_share(r) is None
