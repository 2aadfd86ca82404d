"""The mixed committee's own files at a tiny size on the CPU: the plain
reference (`reference_mixed`) against the specification as the program
encodes it, the `light_mixed` driver on the host route (every device check
reads false by design, every other number compared holds), the control, and
the `mixed_readers` arithmetic on readings made by hand — with a program
that records the lane's spans and with one that does not (the parent of the
PR that added them): nothing to read, never a raise."""

from fractions import Fraction
from types import SimpleNamespace

import pytest

from benchmark import control, control_mixed, fixtures, fixtures_mixed, run
from benchmark import mixed_readers as mx
from benchmark import program_spans as ps
from benchmark import reference_mixed as refm
from benchmark.tests import tiny_mixed

#: what the host route cannot show: no device, so no Edwards row on it
HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs",
                     "edwards_sigs_on_device_minus_range_needed"}
KEY_TYPES = ("ed25519", "secp256k1")


def _chain(n_vals=10):
    return fixtures_mixed.light_chain(7, "mixreftest", 6, n_vals, 10, KEY_TYPES)


# -- the reference ---------------------------------------------------------------------


def test_sign_bytes_and_schemes_equal_the_program_s():
    chain = _chain()
    sizes = {len(v.pub_key.bytes()): v.pub_key.TYPE for v in chain.vals.validators}
    assert sizes == {32: "ed25519", 33: "secp256k1"}
    for lb in chain.blocks:
        c, d = lb.signed_header.commit, chain.commit_data(lb.height)
        for idx, v in enumerate(chain.vals.validators):
            assert refm.scheme_of(d.pubkeys[idx]) == v.pub_key.TYPE
            msg = c.vote_sign_bytes(chain.chain_id, idx)
            sig = d.sigs[idx][2]
            # each scheme's verdict is the program's own, on good and on bad
            assert refm.signature_ok(d.pubkeys[idx], msg, sig) is True
            assert v.pub_key.verify_signature(msg, sig) is True
            assert refm.signature_ok(d.pubkeys[idx], msg + b"x", sig) is False


def test_ecdsa_range_and_low_s_rules_are_written_out():
    chain = _chain()
    d = chain.commit_data(2)
    idx = next(i for i, k in enumerate(d.pubkeys) if len(k) == 33)
    msg = chain.blocks[1].signed_header.commit.vote_sign_bytes(chain.chain_id, idx)
    sig = d.sigs[idx][2]
    r, s = sig[:32], int.from_bytes(sig[32:], "big")
    n = refm.SECP256K1_N
    assert refm.signature_ok(d.pubkeys[idx], msg, sig)
    high = r + (n - s).to_bytes(32, "big")  # plain ECDSA takes it; low-S does not
    assert not refm.signature_ok(d.pubkeys[idx], msg, high)
    pub = chain.vals.validators[idx].pub_key
    assert pub.verify_signature(msg, high) is False
    for bad in (bytes(32) + sig[32:], sig[:32] + bytes(32), n.to_bytes(32, "big") + sig[32:],
                sig[:63], sig + b"\x00"):
        assert refm.signature_ok(d.pubkeys[idx], msg, bad) is False
        assert pub.verify_signature(msg, bad) is False


def test_verdicts_and_counts_by_scheme():
    chain = _chain()
    d = chain.commit_data(3)
    ok, checked, bad, by = refm.commit_verdict(d)
    assert (ok, checked, bad) == (True, 7, -1) and sum(by.values()) == 7
    types = [v.pub_key.TYPE for v in chain.vals.validators]
    assert by == {t: types[:7].count(t) for t in KEY_TYPES}
    for scheme in KEY_TYPES:
        inside = [i for i in range(7) if types[i] == scheme]
        forged = fixtures.commit_data(
            chain.chain_id,
            fixtures.corrupt_commit(chain.blocks[2].signed_header.commit, inside[-1]), chain.vals)
        ok, checked, bad, by = refm.commit_verdict(forged)
        assert (ok, bad, checked) == (False, inside[-1], inside[-1] + 1)
        # a verifier that stops at > 1/2 needs 6 rows: it misses a 7th
        assert refm.commit_verdict(forged, Fraction(1, 2))[0] is (inside[-1] >= 6)
    past = fixtures.commit_data(
        chain.chain_id,
        fixtures.corrupt_commit(chain.blocks[2].signed_header.commit, 8), chain.vals)
    assert refm.commit_verdict(past)[0] is True


def test_child_processes_give_the_same_verdicts():
    chain = fixtures_mixed.light_chain(11, "mixchild", 40, 10, 10, KEY_TYPES)
    commits = [chain.commit_data(h) for h in range(1, 41)]
    commits[17] = fixtures.commit_data(
        chain.chain_id, fixtures.corrupt_commit(chain.blocks[17].signed_header.commit, 3),
        chain.vals)
    here = [refm.commit_verdict(c) for c in commits]
    assert refm.commit_verdicts(commits, workers=2) == here
    assert [v[0] for v in here].count(False) == 1 and here[17][2] == 3


@pytest.mark.parametrize("n_vals,want", [(150, {"ed25519": 50, "secp256k1": 51}),
                                         (20, {"ed25519": 7, "secp256k1": 7}),
                                         (10, {"ed25519": 3, "secp256k1": 4})])
def test_every_seed_draws_the_deals_own_mix_into_the_quorum(n_vals, want):
    """The set sorts by address, so the quorum's mix would move with the
    seed (and a header's cost with it): the seeded set is re-drawn until
    it holds the 1:1 deal's expectation. Same seed, same set."""
    assert fixtures_mixed.quorum_mix(n_vals, KEY_TYPES) == want
    seen = set()
    for seed in (1, 2, 3000003201):
        chain = fixtures_mixed.light_chain(seed, "mixdraw", 2, n_vals, 10, KEY_TYPES)
        rows = fixtures_mixed.quorum_rows(chain.vals, n_vals * 2 // 3 + 1)
        assert {t: len(r) for t, r in rows.items()} == want
        by = refm.commit_verdict(chain.commit_data(2))[3]
        assert by == want
        again = fixtures_mixed.light_chain(seed, "mixdraw", 2, n_vals, 10, KEY_TYPES)
        assert again.vals.hash() == chain.vals.hash()
        seen.add(chain.vals.hash())
    assert len(seen) == 3
    types = [v.pub_key.TYPE for v in chain.vals.validators]
    assert types.count("ed25519") == n_vals // 2  # the deal is still 1:1 over the whole set


def test_seeded_bad_index_is_a_row_of_its_scheme_in_the_last_tenth():
    chain = _chain(30)
    needed = refm.commit_verdict(chain.commit_data(1))[1]
    rows = fixtures_mixed.quorum_rows(chain.vals, needed)
    assert sorted(i for r in rows.values() for i in r) == list(range(needed))
    for scheme in KEY_TYPES:
        for seed in range(5):
            i = fixtures_mixed.seeded_bad_index(seed, "t", rows[scheme], needed)
            assert chain.vals.validators[i].pub_key.TYPE == scheme and i < needed
            tail = [j for j in rows[scheme] if j >= needed - max(1, needed // 10)]
            assert i in tail if tail else i == max(rows[scheme])


# -- the driver, on the host route -----------------------------------------------------


def _failed(res):
    return {k for k, c in res["checks"].items() if not c["ok"]}


def test_host_route_run_holds_every_other_check(tmp_path):
    res = run.execute(tiny_mixed.make_root(str(tmp_path)), tiny_mixed.CELL, 3000003211, 0.3,
                      False, device=tiny_mixed.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS and res["correct"] is False
    # 7 headers a range call, each with the chain's Edwards rows (about half
    # of the 14 the quorum needs), none on a device
    missing = res["checks"]["edwards_sigs_on_device_minus_range_needed"]["value"]
    assert missing > 0 and missing % (7 * (res["attempted"] // 7)) == 0
    assert res["metrics"]["light_headers_per_s"]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0 and res["failed"] == 0


def test_control_lane_answers_true_is_not_correct(tmp_path):
    assert control.CONTROLS["lane_answers_true"] is control_mixed.lane_answers_true
    with control_mixed.lane_answers_true():
        res = run.execute(tiny_mixed.make_root(str(tmp_path)), tiny_mixed.CELL, 3000003213, 0.3,
                          False, device=tiny_mixed.CPU_DEVICE)
    assert _failed(res) == HOST_ROUTE_CHECKS | {"warmup_refusal_height_delta.ecdsa"}
    assert res["checks"]["verdict_mismatches"]["ok"]  # honest traffic reads the same


@pytest.mark.parametrize("renamed,failing", [
    # a device kernel's route carries the scheme in its name: counted, sound
    ("tpu-secp256k1", set()),
    ("device-ecdsa", set()),
    # an Edwards route: the rows are not counted as ECDSA, and `tpu` holds too many
    ("tpu", {"ecdsa_sigs_on_host_minus_needed", "edwards_sigs_on_device_minus_range_needed"}),
    # counted on two routes: each ECDSA sum is right, the total is not
    ("host-ecdsa+tpu-secp256k1",
     {"ecdsa_sigs_on_host_minus_needed", "sigs_verified_minus_needed"}),
])
def test_the_route_check_takes_any_counted_ecdsa_route(tmp_path, monkeypatch, renamed, failing):
    """The lane verifies as it does; its rows are COUNTED on another route, as
    a secp256k1 device kernel's would be (the host route's Edwards count reads
    false by design here, so `tpu` adds nothing new to that one)."""
    from tendermint_tpu.crypto import backend_telemetry as bt

    real = bt.record_route

    def record(route, n):
        for name in (renamed.split("+") if route == "host-ecdsa" else [route]):
            real(name, n)

    monkeypatch.setattr(bt, "record_route", record)
    res = run.execute(tiny_mixed.make_root(str(tmp_path)), tiny_mixed.CELL, 3000003215, 0.3,
                      False, device=tiny_mixed.CPU_DEVICE)
    always = HOST_ROUTE_CHECKS  # no device here: the Edwards rows are on route `cpu`
    assert _failed(res) - always == failing - always
    assert res["checks"]["ecdsa_sigs_on_host_minus_needed"]["ok"] is (
        "ecdsa_sigs_on_host_minus_needed" not in failing)


# -- the readers -----------------------------------------------------------------------


def _recorded(monkeypatch, rows):
    def window_rows(t0, t1):
        return [d for d in rows if d["end"] > t0 and d["start"] < t1]

    monkeypatch.setattr(ps, "window_rows", window_rows)
    return SimpleNamespace(t0=10.0, t1=20.0, stretch=(20.0, 26.0), device_kind="TPU v5 lite",
                           trace=None, units=256, counters={})


def _row(key, start, end, **attrs):
    sub, name = key.split(".", 1)
    return {"subsystem": sub, "name": name, "start": start, "end": end, "attrs": attrs}


def test_lane_readers_on_a_window_made_by_hand(monkeypatch):
    rows = [
        _row("light.verify", 10.0, 13.0), _row("light.verify", 14.0, 17.0),
        _row("validation.collect", 10.0, 10.1, commits=128, sigs=12928, edwards=6400, host=6528),
        _row("validation.collect", 14.0, 14.1, commits=128, sigs=12928, edwards=6400, host=6528),
        _row("batch.host_lane", 10.1, 12.9, n=6528, scheme="secp256k1", workers=1),
        _row("batch.host_lane", 14.1, 16.9, n=6528, scheme="secp256k1", workers=1),
        _row("batch.host_lane_wait", 10.2, 12.9, n=6528),
        _row("batch.host_lane_wait", 14.2, 16.9, n=6528),
        _row("batch.host_lane", 21.0, 23.8, n=6528, scheme="secp256k1", workers=1),  # after it
    ]
    r = _recorded(monkeypatch, rows)
    assert mx.ms_per_ksig(r, "n", "batch.host_lane") == pytest.approx(1e3 * 5.6 / (2 * 6528 / 1e3))
    assert mx.ms_per_unit(r, "batch.host_lane_wait") == pytest.approx(1e3 * 5.4 / 256)
    assert mx.host_lane_share(r) == pytest.approx(100.0 * 5.4 / 6.0)
    assert mx.edwards_row_share(r) == pytest.approx(100.0 * 6400 / 12928)


def test_lane_readers_find_nothing_on_the_parent(monkeypatch):
    """The parent's spans: `validation.collect` without the lane counts, no
    `batch.host_lane*` at all."""
    rows = [_row("light.verify", 10.0, 13.0),
            _row("validation.collect", 10.0, 10.1, commits=128, sigs=0, templates=0)]
    r = _recorded(monkeypatch, rows)
    assert mx.ms_per_ksig(r, "n", "batch.host_lane") is None
    assert mx.ms_per_unit(r, "batch.host_lane_wait") is None
    assert mx.host_lane_share(r) is None and mx.edwards_row_share(r) is None
    r = _recorded(monkeypatch, [])
    assert mx.host_lane_share(r) is None and mx.edwards_row_share(r) is None
