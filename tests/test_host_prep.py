"""The host's share of a device dispatch since PR 28 — one Python pass a
signature (`verify.resolve_rows`), column-wise operands
(`verify.prepare_batch_eq`), resolved and dispatched chunk by chunk —
held to the prep it replaced (`tests/prep_oracle.py`, the parent's bodies):
for the same random bytes the kernel's operands are byte for byte the
same. Then the behaviour the streaming must keep: attribution per chunk,
the first chunk out before the second is resolved, one partition where
there is nothing to partition, and the names the benchmark's harness
hangs its wrappers on.
"""

from __future__ import annotations

import os
import random

import pytest

from tendermint_tpu.crypto.ed25519 import Ed25519PrivKey, Ed25519PubKey
from tendermint_tpu.crypto.tpu import verify as V
from tendermint_tpu.libs import trace

import prep_oracle as oracle

L = V.L
CHUNK = 8192  # verify._MAX_BUCKET as the cells run it


class _Stream:
    """os.urandom's stand-in: a seeded stream, so two preps draw the same
    coefficients."""

    def __init__(self, seed: int):
        self._rng = random.Random(seed)

    def __call__(self, n: int) -> bytes:
        return self._rng.randbytes(n)


def _triples(n: int, n_keys: int, seed: int = 28) -> list[tuple[bytes, bytes, bytes]]:
    """n well-formed (not valid: the prep never looks) ed25519 triples
    over n_keys keys: random R, random s < L, vote-sized messages."""
    rng = random.Random(seed * 1_000_003 + n * 151 + n_keys)
    keys = [rng.randbytes(32) for _ in range(n_keys)]
    out = []
    for i in range(n):
        s = rng.randrange(L)
        out.append((keys[i % n_keys], rng.randbytes(112) + i.to_bytes(8, "little"),
                    rng.randbytes(32) + s.to_bytes(32, "little")))
    return out


def _oracle_rows(items) -> list:
    """The parent's resolve, one object a signature. A key is raw bytes or
    a PubKey; sr25519 goes through the program's own re-expression (the
    parent's did too) into the oracle's object."""
    rows = []
    for pk, msg, sig in items:
        kind = getattr(pk, "TYPE", "ed25519")
        pub = pk if isinstance(pk, bytes) else pk.bytes()
        if kind == "ed25519":
            rows.append(oracle.resolve_ed25519(pub, msg, sig))
        elif kind == "sr25519":
            e = V.resolve_sr25519(pub, msg, sig)
            rows.append(None if e is None else oracle.ResolvedSig(e.a, e.r, e.s, e.k))
        else:
            rows.append(None)
    return rows


def _same(got, want, names) -> None:
    assert len(got) == len(want) == len(names)
    for g, w, name in zip(got, want, names):
        assert g.dtype == w.dtype, name
        assert g.shape == w.shape, name
        assert g.tobytes() == w.tobytes(), name


EQ_NAMES = ("ua_bytes", "r_bytes", "ga_digits", "r_digits", "zs_digits", "s_valid", "gidx")
SIG_NAMES = ("a_bytes", "r_bytes", "s_digits", "h_digits", "s_valid")


def _hold_equal(items, monkeypatch, per_signature: bool = True) -> list:
    """Every chunk of `items`, as the dispatch loop cuts and pads them:
    the new routine's seven operands against the oracle's under the same
    random stream (and the per-signature kernel's five). Returns the new
    rows."""
    n = len(items)
    pad = V._bucket(min(n, CHUNK))
    all_rows = []
    for c, i in enumerate(range(0, n, CHUNK)):
        chunk = items[i : i + CHUNK]
        rows = V.resolve_rows(chunk)
        assert len(rows) == len(chunk)
        want_rows = _oracle_rows(chunk)
        assert [r is None for r in rows] == [r is None for r in want_rows]
        monkeypatch.setattr(os, "urandom", _Stream(1000 + c))
        want = oracle.prepare_batch_eq(want_rows, pad_to=pad)
        monkeypatch.setattr(os, "urandom", _Stream(1000 + c))
        got = V.prepare_batch_eq(rows, pad_to=pad)
        _same(got, want, EQ_NAMES)
        assert got[1].shape == (pad, 32) and got[0].shape[1] == 32
        if per_signature:
            _same(V.prepare_resolved(rows, pad_to=pad),
                  oracle.prepare_resolved(want_rows, pad_to=pad), SIG_NAMES)
        all_rows += rows
    return all_rows


# -- the operands, byte for byte -------------------------------------------------


@pytest.mark.parametrize("n_keys", [1, 101, 150])
@pytest.mark.parametrize("n", [1, 101, 512, 8192, 12928])
def test_operands_equal_the_old_prep(n, n_keys, monkeypatch):
    items = _triples(n, n_keys)
    rows = _hold_equal(items, monkeypatch, per_signature=n <= 512)
    assert None not in rows
    # the served path hands PubKey objects, the cut-off probe raw bytes:
    # one routine, the same rows
    head = items[:300]
    assert V.resolve_rows([(Ed25519PubKey(p), m, s) for p, m, s in head]) == rows[:300]
    # z is never zero and never even: bit 0 of every real row's coefficient
    monkeypatch.setattr(os, "urandom", lambda k: bytes(k))
    r_digits = V.prepare_batch_eq(rows[:CHUNK], pad_to=V._bucket(min(n, CHUNK)))[3]
    assert (r_digits[0, : min(n, CHUNK)] == 1).all() and not r_digits[1:].any()


def _malformed(kind: str, good: tuple[bytes, bytes, bytes]):
    pub, msg, sig = good
    return {
        "key31": (pub[:31], msg, sig),
        "sig63": (pub, msg, sig[:63]),
        "s_eq_L": (pub, msg, sig[:32] + L.to_bytes(32, "little")),
        "s_eq_L_minus_1": (pub, msg, sig[:32] + (L - 1).to_bytes(32, "little")),
        "sig_all_zero": (pub, msg, bytes(64)),
    }[kind]


#: which of the rows above the kernel may take (s < L, sizes right)
_REAL = {"key31": False, "sig63": False, "s_eq_L": False,
         "s_eq_L_minus_1": True, "sig_all_zero": True}


@pytest.mark.parametrize("alone", [False, True], ids=["in101", "alone"])
@pytest.mark.parametrize("kind", list(_REAL))
def test_malformed_rows_stay_inert_and_equal(kind, alone, monkeypatch):
    items = _triples(1 if alone else 101, 7)
    at = 0 if alone else 57
    items[at] = _malformed(kind, items[at])
    rows = _hold_equal(items, monkeypatch)
    assert (rows[at] is not None) == _REAL[kind]
    assert sum(r is None for r in rows) == (0 if _REAL[kind] else 1)
    if not _REAL[kind]:
        eq = V.prepare_batch_eq(rows, pad_to=V._bucket(len(rows)))
        assert not eq[5][at] and not eq[1][at].any() and not eq[3][:, at].any()


def test_all_malformed_chunk_equal(monkeypatch):
    items = [_malformed("s_eq_L" if i % 2 else "sig63", t)
             for i, t in enumerate(_triples(101, 5))]
    rows = _hold_equal(items, monkeypatch)
    assert rows == [None] * 101
    eq = V.prepare_batch_eq(rows, pad_to=128)
    assert eq[0].shape == (63, 32) and not any(a.any() for a in eq)


def test_sr25519_chunk_equal(monkeypatch):
    from tendermint_tpu.crypto.sr25519 import Sr25519PrivKey

    items = [(Ed25519PubKey(p), m, s) for p, m, s in _triples(40, 3)]
    for i in range(6):
        priv = Sr25519PrivKey(bytes([i + 1]) * 32)
        msg = b"sr-%d" % i
        items.insert(5 * i + 2, (priv.pub_key(), msg, priv.sign(msg)))
    bad = bytearray(items[2][2])
    bad[63] &= 0x7F  # the schnorrkel marker bit cleared: malformed
    items[2] = (items[2][0], items[2][1], bytes(bad))
    rows = _hold_equal(items, monkeypatch)
    assert rows[2] is None and sum(r is None for r in rows) == 1
    assert isinstance(rows[7], V.ResolvedSig) and rows[7].k < L


def test_other_key_types_go_to_the_host_rows():
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey

    priv = Secp256k1PrivKey(b"\x09" * 32)
    items = [(Ed25519PubKey(p), m, s) for p, m, s in _triples(6, 2)]
    items.insert(4, (priv.pub_key(), b"k1", priv.sign(b"k1")))
    host_rows: list[int] = []
    rows = V.resolve_rows(items, host_rows, 8192)
    assert rows[4] is None and host_rows == [8192 + 4]
    assert sum(r is None for r in rows) == 1


# -- the streaming -----------------------------------------------------------------


def _signed(n: int, n_keys: int = 8, tag: bytes = b"hp") -> list:
    keys = [Ed25519PrivKey(bytes([i + 1]) * 32) for i in range(n_keys)]
    out = []
    for i in range(n):
        priv = keys[i % n_keys]
        msg = tag + b"-%d" % i
        out.append((priv.pub_key(), msg, priv.sign(msg)))
    return out


@pytest.fixture
def recorder():
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.enabled = old
    trace.RECORDER.clear()


def _spans(recorder, key: str) -> list[dict]:
    rows = [s for s in recorder.dump() if f"{s['subsystem']}.{s['name']}" == key]
    return sorted(rows, key=lambda s: s["start_s"])


def test_bad_signature_in_second_chunk_is_attributed_there_alone(monkeypatch, recorder):
    monkeypatch.setattr(V, "_MAX_BUCKET", 64)
    items = _signed(100)
    pk, msg, sig = items[80]
    items[80] = (pk, msg, sig[:40] + bytes([sig[40] ^ 1]) + sig[41:])
    bv = V.TPUBatchVerifier()
    bv.add_many(items)
    ok, results = bv.verify()
    assert not ok and results == [i != 80 for i in range(100)]
    assert all(type(r) is bool for r in results)
    assert [s["attrs"]["eq_ok"] for s in _spans(recorder, "tpu.collect")] == [True, False]
    # the per-signature kernel ran once, for the 36 rows of chunk 1
    assert [s["attrs"]["n"] for s in _spans(recorder, "tpu.attribute")] == [36]


def test_first_chunk_is_dispatched_before_the_second_is_resolved(monkeypatch, recorder):
    monkeypatch.setattr(V, "_MAX_BUCKET", 64)
    order = []
    real_rows, real_prep = V.resolve_rows, V.prepare_batch_eq

    def rows_spy(items, *a):
        order.append(("resolve", len(items)))
        return real_rows(items, *a)

    def prep_spy(entries, **kw):
        order.append(("prep", len(entries)))
        return real_prep(entries, **kw)

    monkeypatch.setattr(V, "resolve_rows", rows_spy)
    monkeypatch.setattr(V, "prepare_batch_eq", prep_spy)
    items = _signed(150)
    items[140] = (items[140][0], items[140][1], items[140][2][:63])  # found late
    bv = V.TPUBatchVerifier()
    for it in items:
        bv.add(*it)
    assert order == []  # `add` resolves nothing
    ok, results = bv.verify()
    assert results == [i != 140 for i in range(150)] and not ok
    assert order == [("resolve", 64), ("prep", 64), ("resolve", 64), ("prep", 64),
                     ("resolve", 22), ("prep", 22)]
    resolves = _spans(recorder, "tpu.resolve")
    dispatches = _spans(recorder, "tpu.dispatch")
    assert [(s["attrs"]["chunk"], s["attrs"]["n"]) for s in resolves] == [(0, 64), (1, 64), (2, 22)]
    assert len(dispatches) == 3
    for k in (0, 1):  # chunk k's jitted call was made before chunk k+1's resolve began
        end_k = dispatches[k]["start_s"] + dispatches[k]["duration_ms"] / 1e3
        assert end_k <= resolves[k + 1]["start_s"]
    # and nothing is collected before every chunk is in flight
    assert _spans(recorder, "tpu.collect")[0]["start_s"] >= dispatches[-1]["start_s"]


def test_raw_triples_and_verifier_objects_share_the_routine(monkeypatch):
    monkeypatch.setattr(V, "_MAX_BUCKET", 64)
    items = _signed(70)
    raw = [(pk.bytes(), m, s) for pk, m, s in items]
    assert V.verify_batch_eq(raw).tolist() == V.verify_batch_eq(items).tolist() == [True] * 70


# -- the hand-over -----------------------------------------------------------------


def _route_spans(recorder) -> list[dict]:
    return [s["attrs"] for s in _spans(recorder, "batch.route")]


def test_all_edwards_verifier_is_one_partition(recorder):
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb

    items = _signed(12)
    items[5] = (items[5][0], b"another message", items[5][2])
    before = dict(bt.ROUTES).get("cpu", [0.0, 0.0])[1]
    one = cb.AdaptiveBatchVerifier()
    for it in items:
        one.add(*it)
    bulk = cb.AdaptiveBatchVerifier()
    bulk.add_many(items)
    want = (False, [i != 5 for i in range(12)])
    assert one.verify() == bulk.verify() == want
    assert [a["partitions"] for a in _route_spans(recorder)] == [1, 1]
    assert [a["n"] for a in _route_spans(recorder)] == [12, 12]
    assert bt.ROUTES["cpu"][1] - before == 24  # the routed signatures are still counted
    assert cb.AdaptiveBatchVerifier().verify() == (False, [])


def test_mixed_edwards_and_bls_verifier_is_two_partitions(recorder):
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto.bls import BLSPrivKey

    edwards = _signed(6)
    edwards[2] = (edwards[2][0], b"not what was signed", edwards[2][2])
    alone = cb.AdaptiveBatchVerifier()
    alone.add_many(edwards)
    _, want = alone.verify()
    priv = BLSPrivKey(b"\x21" * 32)
    mixed_items = list(edwards)
    mixed_items.insert(3, (priv.pub_key(), b"bls", priv.sign(b"bls")))
    mixed = cb.AdaptiveBatchVerifier()
    mixed.add_many(mixed_items)
    ok, got = mixed.verify()
    assert not ok and got[3] is True and got[:3] + got[4:] == want
    assert [(a["partitions"], a["n"]) for a in _route_spans(recorder)] == [(1, 6), (2, 6)]


def test_add_many_takes_what_add_takes(recorder):
    """A key type with no batch kernel is no longer refused: its rows go
    down the host lane beside the Edwards partition, verdicts in the
    caller's order, the partition count on the one `batch.route`."""
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto.secp256k1 import Secp256k1PrivKey

    priv = Secp256k1PrivKey(b"\x09" * 32)
    good = (priv.pub_key(), b"m", priv.sign(b"m"))
    bad = (priv.pub_key(), b"other", good[2])
    one = cb.AdaptiveBatchVerifier()
    for it in _signed(3) + [good]:
        one.add(*it)
    assert one.verify() == (True, [True] * 4)
    many = cb.AdaptiveBatchVerifier()
    many.add_many([bad] + _signed(3) + [good])
    assert many.verify() == (False, [False, True, True, True, True])
    assert [(a["partitions"], a["n"]) for a in _route_spans(recorder)] == [(2, 3), (2, 3)]


def test_commit_verifier_hands_its_list_over_in_one_step(monkeypatch):
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.types import validation

    calls = []
    real = cb.AdaptiveBatchVerifier.add_many
    monkeypatch.setattr(cb.AdaptiveBatchVerifier, "add_many",
                        lambda self, items: (calls.append(len(items)), real(self, items))[1])
    monkeypatch.setattr(cb.AdaptiveBatchVerifier, "add",
                        lambda *a: pytest.fail("one add a signature"))
    items = _signed(9)
    cv = validation._CommitVerifier()
    for it in items:
        cv.add(*it)
    assert cv.verify() == (True, [True] * 9) and calls == [9] and cv.via == "local"


# -- what the benchmark reads by name ------------------------------------------------


def test_harness_wrappers_still_read_every_dispatch(monkeypatch):
    """`benchmark/harness.host_prep_spans` hangs a span on
    `verify.prepare_batch_eq` (and notes n, bucket, groups from its first
    argument and its result) and a timer on `verify.resolve`: both names
    stay module attributes looked up at call time, one prep a dispatch."""
    from benchmark import harness
    from tendermint_tpu.crypto import batch as cb

    monkeypatch.setattr(V, "_MAX_BUCKET", 64)
    monkeypatch.setattr(cb, "MIN_TPU_BATCH", 2)
    monkeypatch.setattr(cb, "_tpu_available", True)
    items = _signed(100, n_keys=5)
    items[3] = (items[3][0], items[3][1], items[3][2][:63])
    items[70] = (items[70][0], items[70][1], items[70][2][:32] + L.to_bytes(32, "little"))
    patches, spans = harness.Patches(), harness.Spans()
    harness.host_prep_spans(patches, spans)
    try:
        bv = cb.AdaptiveBatchVerifier()
        bv.add_many(items)
        ok, results = bv.verify()
    finally:
        patches.undo()
    assert bv.last_route == "tpu"
    assert not ok and results == [i not in (3, 70) for i in range(100)]
    assert [row[3] for row in spans.select("host_prep")] == [
        {"n": 63, "bucket": 64, "groups": 63}, {"n": 35, "bucket": 64, "groups": 63}]
    assert callable(V.resolve) and V.resolve(*items[0]) is not None
