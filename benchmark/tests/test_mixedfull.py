"""The mixed committee behind the hub (`mixedfull150.blocksync`): its own files
at a tiny size on the CPU — what the fixture child hands over against what the
same driver builds in this process (keys, sets, transactions, verdicts and the
ed25519 signatures: OpenSSL draws a nonce an ECDSA signature, so secp256k1
BYTES differ from build to build, and block hashes with them from height 2
on), the route checks on other routes' names, and the `mixedsync_readers`
arithmetic on readings made by hand — with a program that records the lane
under `hub.dispatch` and with one whose hub verifies such rows in a loop of its
own (the parent of the PR that added the cell): nothing to read, never a raise.
Tier-1's `tests/test_mixedfull150.py` drives the cell itself."""

import importlib
import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, run
from benchmark import mixedsync_readers as ms
from benchmark import program_spans as ps
from benchmark import reference_mixedfull as refmf
from benchmark.tests import tiny_mixedfull

SEED = 3000004151
HOST_ROUTE_CHECKS = {"probe_errors", "tpu_route_sigs",
                     "edwards_sigs_on_device_minus_range_needed"}


def _sigs(commit, vals, scheme):
    return [cs.signature for cs, v in zip(commit.signatures, vals.validators)
            if v.pub_key.TYPE == scheme]


def test_the_child_hands_over_what_the_seed_pins(tmp_path):
    root = tiny_mixedfull.make_root(str(tmp_path), blocks=40)
    _bench, cell, cfg, _layer = run.load_cell(root, tiny_mixedfull.CELL)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    child = harness.fixture_builder(driver, root, tiny_mixedfull.CELL, cfg, cell, SEED)
    try:
        theirs = child.take()
        child.finish(theirs)
    finally:
        child.close()
    mine = harness.assemble(driver.build(cfg, cell, SEED))
    assert driver.FIXTURE == "child" and isinstance(child, harness.FixtureChild)
    assert mine.warm_bad == theirs.warm_bad
    for here, there in ((mine.chain, theirs.chain), (mine.warm, theirs.warm)):
        assert there.n_blocks == here.n_blocks == len(there.wire)
        assert all(type(v) is bytes for v in there.wire.values())
        assert there.vals.hash() == here.vals.hash()
        assert there.genesis.to_json() == here.genesis.to_json()
        assert there.genesis.consensus_params.validator.pub_key_types == (
            "ed25519", "secp256k1")
        assert there.txs_at == here.txs_at and there.app_hash_at == here.app_hash_at
        assert there.block_hash_at[1] == here.block_hash_at[1]  # no LastCommit in block 1
        for h in (1, 7, there.n_blocks):
            a, b = there.commit(h), here.commit(h)
            # a vote signs the block's ID: pinned for block 1 alone
            assert (_sigs(a, there.vals, "ed25519") == _sigs(b, here.vals, "ed25519")) is (h == 1)
            assert len(_sigs(a, there.vals, "secp256k1")) == len(
                _sigs(b, here.vals, "secp256k1")) == 3
            assert refmf.commit_verdict(there.commit_data(h)) == refmf.commit_verdict(
                here.commit_data(h)) == (True, 5, -1, {"ed25519": 2, "secp256k1": 3})
            assert there.block(h).hash() == there.block_hash_at[h]


@pytest.mark.parametrize("renamed,failing", [
    # a device kernel's route carries the scheme in its name: counted, sound
    ("tpu-secp256k1", set()),
    # an Edwards route: the rows are not counted as ECDSA
    ("cpu", {"ecdsa_sigs_routed_minus_needed"}),
    # a lane that runs twice reads high
    ("host-ecdsa+device-ecdsa", {"ecdsa_sigs_routed_minus_needed"}),
])
def test_the_route_check_takes_any_counted_ecdsa_route(tmp_path, monkeypatch, renamed, failing):
    from tendermint_tpu.crypto import backend_telemetry as bt

    real = bt.record_route

    def record(route, n):
        for name in (renamed.split("+") if route == "host-ecdsa" else [route]):
            real(name, n)

    monkeypatch.setattr(bt, "record_route", record)
    res = run.execute(tiny_mixedfull.make_root(str(tmp_path)), tiny_mixedfull.CELL, SEED + 1,
                      1.5, False, device=tiny_mixedfull.CPU_DEVICE)
    assert {k for k, c in res["checks"].items() if not c["ok"]} - HOST_ROUTE_CHECKS == failing


# -- the readers -----------------------------------------------------------------------


def _recorded(monkeypatch, rows, counters=None):
    def window_rows(t0, t1):
        return [d for d in rows if d["end"] > t0 and d["start"] < t1]

    monkeypatch.setattr(ps, "window_rows", window_rows)
    return SimpleNamespace(t0=10.0, t1=20.0, stretch=(20.0, 25.0), device_kind="TPU v5 lite",
                           trace=None, units=128, counters=counters or {})


def _row(key, start, end, **attrs):
    sub, name = key.split(".", 1)
    return {"subsystem": sub, "name": name, "start": start, "end": end, "attrs": attrs}


def test_lane_readers_on_a_window_made_by_hand(monkeypatch):
    rows = [
        _row("blocksync.verify", 10.0, 10.5), _row("blocksync.verify", 14.0, 14.5),
        _row("validation.collect", 10.0, 10.03, commits=64, sigs=6464, edwards=3200, host=3264),
        _row("validation.collect", 14.0, 14.03, commits=64, sigs=6464, edwards=3200, host=3264),
        _row("batch.host_lane", 10.1, 10.4, n=3264, scheme="secp256k1", workers=13),
        _row("batch.host_lane", 14.1, 14.4, n=3264, scheme="secp256k1", workers=13),
        _row("batch.host_lane_wait", 10.2, 10.4, n=3264),
        _row("batch.host_lane_wait", 14.2, 14.4, n=3264),
        _row("batch.host_lane", 21.0, 21.3, n=3264, scheme="secp256k1", workers=13),  # after it
    ]
    r = _recorded(monkeypatch, rows, {"hub.scheme_host_sigs": 6528.0, "hub.dispatches": 2.0,
                                      "hub.dispatched_sigs": 12928.0})
    assert ms.ms_per_ksig(r, "n", "batch.host_lane") == pytest.approx(1e3 * 0.6 / 6.528)
    assert ms.ms_per_unit(r, "batch.host_lane_wait") == pytest.approx(1e3 * 0.4 / 128)
    assert ms.ms_per_unit(r, "blocksync.verify") == pytest.approx(1e3 * 1.0 / 128)
    assert ms.host_lane_share(r) == pytest.approx(40.0)
    assert ms.edwards_row_share(r) == pytest.approx(100.0 * 3200 / 6464)
    assert ms.counter_ratio(r, "hub.scheme_host_sigs", "hub.dispatches") == 3264.0


def test_lane_readers_find_nothing_on_the_parent(monkeypatch):
    """The parent's hub: the same `validation.collect` (rows by lane since
    PR 32), no `batch.host_lane*` under its dispatches, no `scheme_host_sigs`."""
    rows = [_row("blocksync.verify", 10.0, 12.0),
            _row("validation.collect", 10.0, 10.03, commits=64, sigs=6464, edwards=3200,
                 host=3264)]
    r = _recorded(monkeypatch, rows, {"hub.dispatches": 1.0, "hub.dispatched_sigs": 6464.0})
    assert ms.ms_per_ksig(r, "n", "batch.host_lane") is None
    assert ms.ms_per_unit(r, "batch.host_lane_wait") is None
    assert ms.host_lane_share(r) is None
    assert ms.counter_ratio(r, "hub.scheme_host_sigs", "hub.dispatches") is None
    assert ms.edwards_row_share(r) == pytest.approx(100.0 * 3200 / 6464)
    r = _recorded(monkeypatch, [])
    assert ms.host_lane_share(r) is None and ms.edwards_row_share(r) is None


def test_every_mixedsync_file_takes_its_arithmetic_from_two_modules():
    """The harness's own tests count the metric files that name the recorder's
    reader (`test_program_spans`) and the on-CPU one (`test_cpu_readers`) by
    name: the `.mixedsync` files name neither."""
    bench = json.load(open(os.path.join(harness.ROOT, "BENCHMARK.json")))
    mine = [m for m in bench["per_layer"] if m["name"].endswith(".mixedsync")]
    assert len(mine) == 24
    for m in mine:
        assert m["workloads"] == ["mixedfull150.blocksync"]
        assert m["moves"] == "blocksync_blocks_per_s"
        text = open(os.path.join(harness.ROOT, "benchmark", "metrics", m["name"] + ".py")).read()
        assert "program_spans" not in text and "cpu_readers" not in text, m["name"]
        assert ("from benchmark import mixedsync_readers" in text) != (
            "from benchmark import readers" in text), m["name"]
