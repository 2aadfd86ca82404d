"""The folded wire encoder against a slow reference (ISSUE 26).

`libs/protoenc`, the repeated messages of a 150-validator chain
(`CommitSig`, `Commit`, `Validator`, `ValidatorSet`) and the per-commit
sign-bytes template make every constant once. The oracle below is the loop
encoder they replaced, kept here verbatim: one varint loop per value, one
`tag()` per field, `out +=` per element, the whole canonical vote per
signature. Same bytes for every input, same errors; and nothing remembered
between calls.
"""

import hashlib
import random
import struct

import pytest

from tendermint_tpu import testing as tt
from tendermint_tpu.consensus import wire_gen
from tendermint_tpu.crypto import PUBKEY_PROTO_FIELD, merkle
from tendermint_tpu.libs import protoenc as pe
from tendermint_tpu.libs import trace
from tendermint_tpu.light.client import TrustedStore
from tendermint_tpu.light.types import LightBlock, SignedHeader
from tendermint_tpu.types import block as block_mod
from tendermint_tpu.types import canonical, validation
from tendermint_tpu.types import validator_set as vs_mod
from tendermint_tpu.types.block import (
    NIL_BLOCK_ID,
    BlockID,
    Commit,
    CommitSig,
    Header,
    PartSetHeader,
)
from tendermint_tpu.types.keys import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    SignedMsgType,
)
from tendermint_tpu.types.validator_set import Validator, ValidatorSet

# -- the oracle: the loop encoder as it was before ISSUE 26 ------------------------


def ref_uvarint(value: int) -> bytes:
    if value < 0:
        raise ValueError("uvarint requires a non-negative value")
    out = bytearray()
    while True:
        b = value & 0x7F
        value >>= 7
        if value:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def ref_tag(field_number: int, wire_type: int) -> bytes:
    return ref_uvarint((field_number << 3) | wire_type)


def ref_varint_field(field_number: int, value: int) -> bytes:
    if value == 0:
        return b""
    if value < 0:
        value &= (1 << 64) - 1
    return ref_tag(field_number, 0) + ref_uvarint(value)


def ref_sfixed64_field(field_number: int, value: int) -> bytes:
    if value == 0:
        return b""
    return ref_tag(field_number, 1) + struct.pack("<q", value)


def ref_fixed64_field(field_number: int, value: int) -> bytes:
    if value == 0:
        return b""
    return ref_tag(field_number, 1) + struct.pack("<Q", value)


def ref_bytes_field(field_number: int, value: bytes) -> bytes:
    if not value:
        return b""
    return ref_tag(field_number, 2) + ref_uvarint(len(value)) + value


def ref_string_field(field_number: int, value: str) -> bytes:
    return ref_bytes_field(field_number, value.encode("utf-8"))


def ref_message_field(field_number: int, encoded: bytes) -> bytes:
    return ref_tag(field_number, 2) + ref_uvarint(len(encoded)) + encoded


def ref_len_prefixed(encoded: bytes) -> bytes:
    return ref_uvarint(len(encoded)) + encoded


def ref_encode_timestamp(ns: int) -> bytes:
    seconds, nanos = divmod(ns, 1_000_000_000)
    return ref_varint_field(1, seconds) + ref_varint_field(2, nanos)


def ref_part_set_header(psh) -> bytes:
    return ref_varint_field(1, psh.total) + ref_bytes_field(2, psh.hash)


def ref_block_id(bid) -> bytes:
    return ref_bytes_field(1, bid.hash) + ref_message_field(
        2, ref_part_set_header(bid.part_set_header)
    )


def ref_commit_sig(cs) -> bytes:
    out = ref_varint_field(1, cs.flag)
    out += ref_bytes_field(2, cs.validator_address)
    out += ref_message_field(3, ref_encode_timestamp(cs.timestamp_ns))
    out += ref_bytes_field(4, cs.signature)
    return out


def ref_commit(c) -> bytes:
    out = ref_sfixed64_field(1, c.height)
    out += ref_sfixed64_field(2, c.round)
    out += ref_message_field(3, ref_block_id(c.block_id))
    for cs in c.signatures:
        out += ref_message_field(4, ref_commit_sig(cs))
    if c.agg_sig:
        out += ref_bytes_field(5, c.agg_sig)
    return out


def ref_commit_hash(c) -> bytes:
    leaves = [ref_commit_sig(cs) for cs in c.signatures]
    if c.agg_sig:
        leaves.append(c.agg_sig)
    return merkle.hash_from_byte_slices(leaves)


def ref_pubkey_to_proto(pub) -> bytes:
    return ref_bytes_field(PUBKEY_PROTO_FIELD[pub.TYPE], pub.bytes())


def ref_validator_simple(v) -> bytes:
    out = ref_message_field(1, ref_pubkey_to_proto(v.pub_key))
    out += ref_varint_field(2, v.voting_power)
    return out


def ref_validator(v) -> bytes:
    return ref_validator_simple(v) + ref_sfixed64_field(3, v.proposer_priority)


def ref_validator_set(vals) -> bytes:
    out = b""
    for v in vals.validators:
        out += ref_message_field(1, ref_validator(v))
    if vals._proposer is not None:
        out += ref_bytes_field(2, vals._proposer.address)
    return out


def ref_validator_set_hash(vals) -> bytes:
    return merkle.hash_from_byte_slices(
        [ref_validator_simple(v) for v in vals.validators]
    )


def ref_vote_sign_bytes(chain_id, msg_type, height, round_, block_id, timestamp_ns):
    out = ref_varint_field(1, int(msg_type))
    out += ref_sfixed64_field(2, height)
    out += ref_sfixed64_field(3, round_)
    if block_id is not None and not block_id.is_nil():
        cbid = ref_bytes_field(1, block_id.hash) + ref_message_field(
            2,
            ref_varint_field(1, block_id.part_set_header.total)
            + ref_bytes_field(2, block_id.part_set_header.hash),
        )
        out += ref_message_field(4, cbid)
    out += ref_message_field(5, ref_encode_timestamp(timestamp_ns))
    out += ref_string_field(6, chain_id)
    return ref_len_prefixed(out)


def ref_commit_vote_sign_bytes(commit, chain_id, idx):
    cs = commit.signatures[idx]
    return ref_vote_sign_bytes(
        chain_id,
        SignedMsgType.PRECOMMIT,
        commit.height,
        commit.round,
        commit.block_id if cs.flag == BLOCK_ID_FLAG_COMMIT else NIL_BLOCK_ID,
        cs.timestamp_ns,
    )


# -- seeded cases ------------------------------------------------------------------

T0 = 1_700_000_000_000_000_000
BID = BlockID(hashlib.sha256(b"block").digest(),
              PartSetHeader(3, hashlib.sha256(b"parts").digest()))
CHAIN_50 = "c" * 50  # the longest chain ID a header admits


def _rng(tag: str) -> random.Random:
    return random.Random(f"wire-encoders-{tag}")


def _sig(rng, flag=BLOCK_ID_FLAG_COMMIT, ts=None, addr=20, sig=64) -> CommitSig:
    if ts is None:
        # every signature its own second AND its own nanoseconds
        ts = T0 + rng.randrange(10**12)
    return CommitSig(flag, rng.randbytes(addr), ts, rng.randbytes(sig))


def _commit(tag, n, height=7, round_=1, agg=False, flags=None) -> Commit:
    rng = _rng(tag)
    sigs = []
    for i in range(n):
        flag = flags[i % len(flags)] if flags else BLOCK_ID_FLAG_COMMIT
        sigs.append(CommitSig() if flag == BLOCK_ID_FLAG_ABSENT else _sig(rng, flag))
    return Commit(height, round_, BID, tuple(sigs), rng.randbytes(96) if agg else b"")


MIXED = (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL,
         BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_ABSENT)

#: what a Commit keeps of its own serialisation since ISSUE 42
COMMIT_MEMOS = {"_sig_bytes", "_encoded", "_hash"}

COMMITS = {
    "no-sigs": _commit("c0", 0),
    "one-sig": _commit("c1", 1),
    "150-sigs": _commit("c150", 150),
    "150-mixed-flags": _commit("c150m", 150, flags=MIXED),
    "height-0": _commit("ch0", 4, height=0),
    "round-0": _commit("cr0", 4, round_=0),
    "height-and-round-0": _commit("chr0", 2, height=0, round_=0),
    "aggregate": Commit(
        9, 2, BID,
        tuple(CommitSig(cs.flag, cs.validator_address, cs.timestamp_ns, b"")
              for cs in _commit("cagg", 150, flags=MIXED).signatures),
        _rng("agg").randbytes(96),
    ),
    "aggregate-one": _commit("cagg1", 1, agg=True),
    "nil-block-id": Commit(3, 0, NIL_BLOCK_ID, _commit("cnil", 3).signatures),
    "negative-height": Commit(-5, -1, BID, _commit("cneg", 2).signatures),
}

COMMIT_SIGS = {
    "absent": CommitSig(),
    "commit": _sig(_rng("s1")),
    "nil": _sig(_rng("s2"), BLOCK_ID_FLAG_NIL),
    "empty-address": _sig(_rng("s3"), addr=0),
    "empty-signature": _sig(_rng("s4"), sig=0),
    "timestamp-0": _sig(_rng("s5"), ts=0),
    "whole-seconds": _sig(_rng("s6"), ts=1_700_000_000 * 10**9),
    "sub-second": _sig(_rng("s7"), ts=999_999_999),
    "one-nanosecond": _sig(_rng("s8"), ts=1),
    "before-the-epoch": _sig(_rng("s9"), ts=-1_500_000_000),
    "bls-signature": _sig(_rng("s10"), sig=96),
    "long-signature": _sig(_rng("s11"), sig=200),
    "flag-200": _sig(_rng("s12"), flag=200),
}


def _validators(n, tag="v") -> list[Validator]:
    vals, _ = tt.make_validator_set(n, seed=tag.encode())
    return vals.validators


_PUB = _validators(1)[0].pub_key

VALIDATORS = {
    "priority-0": Validator(_PUB, 10, 0),
    "priority-negative": Validator(_PUB, 10, -7),
    "priority-plus-2^62": Validator(_PUB, 10, 1 << 62),
    "priority-minus-2^62": Validator(_PUB, 10, -(1 << 62)),
    "power-0": Validator(_PUB, 0, 5),
    "power-1": Validator(_PUB, 1, 5),
    "power-127": Validator(_PUB, 127, 5),
    "power-128": Validator(_PUB, 128, 5),
    "power-2^62": Validator(_PUB, 1 << 62, 5),
}


def _set_without_proposer(n) -> ValidatorSet:
    vals = ValidatorSet(_validators(n))
    return ValidatorSet.decode(
        b"".join(pe.message_field(1, v.encode()) for v in vals.validators)
    )


def _mixed_key_set() -> ValidatorSet:
    vals, _ = tt.make_validator_set(6, key_types=("ed25519", "secp256k1"))
    return vals


VALIDATOR_SETS = {
    "150-with-proposer": lambda: ValidatorSet(_validators(150)),
    "150-rotated": lambda: ValidatorSet(_validators(150)).copy_increment_proposer_priority(17),
    "150-without-proposer": lambda: _set_without_proposer(150),
    "one": lambda: ValidatorSet(_validators(1)),
    "empty": lambda: ValidatorSet([]),
    "mixed-key-types": _mixed_key_set,
}


# -- libs/protoenc -----------------------------------------------------------------

UVARINTS = [0, 1, 127, 128, 255, 300, 16383, 16384, 2**21 - 1, 2**21, 2**32,
            2**63 - 1, 2**63, 2**64 - 1, 2**64, 2**70]


@pytest.mark.parametrize("value", UVARINTS)
def test_uvarint(value):
    assert pe.uvarint(value) == ref_uvarint(value)
    r = pe.Reader(pe.uvarint(value))
    if value < 2**70:
        assert r.read_uvarint() == value and r.eof()


@pytest.mark.parametrize("value", [-1, -128, -(2**63)])
def test_uvarint_refuses_a_negative_value(value):
    with pytest.raises(ValueError, match="non-negative"):
        ref_uvarint(value)
    with pytest.raises(ValueError, match="non-negative"):
        pe.uvarint(value)


@pytest.mark.parametrize("value", [0, 1, 127, 128, -1, -127, -128, 2**63 - 1, -(2**63)])
def test_varint_of_an_int64(value):
    # what varint_field writes after its tag
    assert pe.varint(value) == ref_uvarint(value & (2**64 - 1) if value < 0 else value)


@pytest.mark.parametrize("field", [1, 2, 15, 16, 31, 32, 2047, 2048, 2**28])
@pytest.mark.parametrize("wire_type", [0, 1, 2, 5])
def test_tag(field, wire_type):
    assert pe.tag(field, wire_type) == ref_tag(field, wire_type)


def test_tag_refuses_a_negative_field_number():
    with pytest.raises(ValueError):
        ref_tag(-1, 0)
    with pytest.raises(ValueError):
        pe.tag(-1, 0)


@pytest.mark.parametrize("kind, wire_type", [
    ("varint", 0), ("sfixed64", 1), ("fixed64", 1), ("bytes", 2), ("message", 2)])
def test_field_tag_is_the_tag_its_helper_writes(kind, wire_type):
    for field in (1, 5, 15, 16, 100):
        assert pe.field_tag(field, kind) == ref_tag(field, wire_type)
    with pytest.raises(KeyError):
        pe.field_tag(1, "tag")


FIELD_NUMBERS = [1, 15, 16, 31, 32, 5000]


@pytest.mark.parametrize("field", FIELD_NUMBERS)
@pytest.mark.parametrize(
    "value", [0, 1, 127, 128, 2**63 - 1, 2**64 - 1, -1, -128, -(2**63)])
def test_varint_field(field, value):
    assert pe.varint_field(field, value) == ref_varint_field(field, value)


@pytest.mark.parametrize("field", FIELD_NUMBERS)
def test_fixed_width_fields(field):
    for value in (0, 1, -1, 2**62, -(2**62), 2**63 - 1, -(2**63)):
        assert pe.sfixed64_field(field, value) == ref_sfixed64_field(field, value)
    for value in (0, 1, 2**63, 2**64 - 1):
        assert pe.fixed64_field(field, value) == ref_fixed64_field(field, value)
    for fn, value in ((pe.sfixed64_field, 2**63), (pe.fixed64_field, -1),
                      (pe.fixed64_field, 2**64)):
        with pytest.raises(struct.error):
            fn(field, value)
    assert pe.bool_field(field, True) == ref_varint_field(field, 1)
    assert pe.bool_field(field, False) == b""


@pytest.mark.parametrize("field", FIELD_NUMBERS)
@pytest.mark.parametrize("size", [0, 1, 20, 64, 127, 128, 16383, 16384, 70000])
def test_length_delimited_fields(field, size):
    data = _rng(f"ld{size}").randbytes(size)
    assert pe.bytes_field(field, data) == ref_bytes_field(field, data)
    assert pe.message_field(field, data) == ref_message_field(field, data)
    assert pe.len_prefixed(data) == ref_len_prefixed(data)
    text = "é" * (size // 2)
    assert pe.string_field(field, text) == ref_string_field(field, text)


# -- types: the repeated messages of a commit and of a validator set ---------------


@pytest.mark.parametrize("ns", [
    0, 1, 999_999_999, 10**9, 10**9 + 1, 127 * 10**9, 128 * 10**9, T0, T0 + 149,
    T0 + 999_999_999, -1, -(10**9), -T0])
def test_encode_timestamp(ns):
    assert canonical.encode_timestamp(ns) == ref_encode_timestamp(ns)
    assert wire_gen.encode_timestamp(ns) == ref_encode_timestamp(ns)


@pytest.mark.parametrize("name", COMMIT_SIGS)
def test_commit_sig_encode(name):
    cs = COMMIT_SIGS[name]
    assert cs.encode() == ref_commit_sig(cs)
    assert wire_gen.encode_commit_sig(cs) == ref_commit_sig(cs)
    if cs.timestamp_ns >= 0:  # the reader has never known a negative second
        assert CommitSig.decode(cs.encode()) == cs


@pytest.mark.parametrize("name", COMMITS)
def test_commit_encode(name):
    c = COMMITS[name]
    assert c.encode() == ref_commit(c)
    assert wire_gen.encode_commit(c) == c.encode()
    assert Commit.decode(c.encode()) == c


@pytest.mark.parametrize("name", COMMITS)
def test_commit_hash(name):
    c = COMMITS[name]
    assert c.hash() == ref_commit_hash(c)


@pytest.mark.parametrize("name", VALIDATORS)
def test_validator_encode(name):
    v = VALIDATORS[name]
    assert v.simple_encode() == ref_validator_simple(v)
    assert v.encode() == ref_validator(v)


@pytest.mark.parametrize("priority", [2**63, -(2**63) - 1])
def test_validator_priority_out_of_range_raises_as_before(priority):
    v = Validator(_PUB, 10, priority)
    with pytest.raises(struct.error):
        ref_validator(v)
    with pytest.raises(struct.error):
        v.encode()


@pytest.mark.parametrize("name", VALIDATOR_SETS)
def test_validator_set_encode(name):
    vals = VALIDATOR_SETS[name]()
    assert (vals._proposer is None) == (name in ("150-without-proposer", "empty"))
    assert vals.encode() == ref_validator_set(vals)
    again = ValidatorSet.decode(vals.encode())
    assert again.encode() == vals.encode()


@pytest.mark.parametrize("name", VALIDATOR_SETS)
def test_validator_set_hash(name):
    vals = VALIDATOR_SETS[name]()
    assert vals.hash() == ref_validator_set_hash(vals)


@pytest.mark.parametrize("n_txs", [0, 1, 150])
def test_block_encode_joins_its_transactions(n_txs):
    rng = _rng(f"txs{n_txs}")
    txs = tuple(rng.randbytes(rng.choice((1, 30, 127, 128, 400))) for _ in range(n_txs))
    header = Header(chain_id="chain", height=5, time_ns=T0,
                    validators_hash=hashlib.sha256(b"v").digest())
    block = block_mod.Block(header, txs, (), COMMITS["150-mixed-flags"])
    expected = ref_message_field(1, header.encode())
    for tx in txs:
        expected += ref_message_field(2, tx)
    expected += ref_message_field(3, ref_commit(block.last_commit))
    assert block.encode() == expected
    assert block_mod.Block.decode(block.encode()) == block


# -- the per-commit sign-bytes template --------------------------------------------

SIGN_CASES = {
    "precommit": (SignedMsgType.PRECOMMIT, 7, 1, BID),
    "prevote": (SignedMsgType.PREVOTE, 7, 1, BID),
    "nil-block-id": (SignedMsgType.PRECOMMIT, 7, 1, NIL_BLOCK_ID),
    "no-block-id": (SignedMsgType.PRECOMMIT, 7, 1, None),
    "height-0": (SignedMsgType.PRECOMMIT, 0, 1, BID),
    "round-0": (SignedMsgType.PRECOMMIT, 7, 0, BID),
    "height-2^62": (SignedMsgType.PRECOMMIT, 1 << 62, 2**31, BID),
    "hash-only-block-id": (SignedMsgType.PRECOMMIT, 7, 1, BlockID(BID.hash)),
    "parts-only-block-id": (SignedMsgType.PRECOMMIT, 7, 1,
                            BlockID(b"", BID.part_set_header)),
}


@pytest.mark.parametrize("chain_id", ["", "c", "test-chain", CHAIN_50])
@pytest.mark.parametrize("name", SIGN_CASES)
def test_vote_sign_bytes(name, chain_id):
    msg_type, height, round_, block_id = SIGN_CASES[name]
    template = canonical.vote_sign_template(chain_id, msg_type, height, round_, block_id)
    for ts in (0, 1, 999_999_999, 10**9, T0, T0 + 123_456_789, -1):
        expected = ref_vote_sign_bytes(chain_id, msg_type, height, round_, block_id, ts)
        assert canonical.vote_sign_bytes(
            chain_id, msg_type, height, round_, block_id, ts) == expected
        assert template(ts) == expected


def test_vote_sign_bytes_is_the_template_applied_once(monkeypatch):
    made = []
    real = canonical.vote_sign_template

    def counting(*args):
        made.append(args)
        return real(*args)

    monkeypatch.setattr(canonical, "vote_sign_template", counting)
    got = canonical.vote_sign_bytes("chain", SignedMsgType.PRECOMMIT, 7, 1, BID, T0)
    assert made == [("chain", SignedMsgType.PRECOMMIT, 7, 1, BID)]
    assert got == ref_vote_sign_bytes("chain", SignedMsgType.PRECOMMIT, 7, 1, BID, T0)


@pytest.mark.parametrize("chain_id", ["test-chain", CHAIN_50])
@pytest.mark.parametrize("name", ["150-sigs", "150-mixed-flags", "aggregate",
                                  "one-sig", "height-0", "round-0", "nil-block-id"])
def test_commit_sign_bytes_by_index(name, chain_id):
    c = COMMITS[name]
    stamps = [cs.timestamp_ns for cs in c.signatures if cs.flag != BLOCK_ID_FLAG_ABSENT]
    assert len(set(stamps)) == len(stamps)  # DISTINCT timestamps
    sign_bytes = c.sign_bytes(chain_id)
    assert sign_bytes.templates == 0  # built only when first needed
    for idx, cs in enumerate(c.signatures):
        expected = ref_commit_vote_sign_bytes(c, chain_id, idx)
        assert sign_bytes(idx) == expected
        assert c.vote_sign_bytes(chain_id, idx) == expected
        assert canonical.vote_sign_bytes(
            chain_id, SignedMsgType.PRECOMMIT, c.height, c.round,
            cs.block_id(c.block_id), cs.timestamp_ns) == expected
    flags = {cs.flag == BLOCK_ID_FLAG_COMMIT for cs in c.signatures}
    assert sign_bytes.templates == len(flags)  # one for block votes, one for the rest
    with pytest.raises(IndexError):
        sign_bytes(len(c.signatures))


def test_commit_sign_bytes_builds_a_template_per_flag_when_first_needed(monkeypatch):
    made = []
    real = block_mod.vote_sign_template

    def counting(chain_id, msg_type, height, round_, block_id):
        made.append(block_id)
        return real(chain_id, msg_type, height, round_, block_id)

    monkeypatch.setattr(block_mod, "vote_sign_template", counting)
    c = COMMITS["150-mixed-flags"]  # commit, commit, nil, commit, absent, ...
    sign_bytes = c.sign_bytes("test-chain")
    assert made == []
    sign_bytes(0), sign_bytes(1)
    assert made == [BID] and sign_bytes.templates == 1
    sign_bytes(2), sign_bytes(3), sign_bytes(7)
    assert made == [BID, NIL_BLOCK_ID] and sign_bytes.templates == 2
    for idx in range(150):
        sign_bytes(idx)
    assert len(made) == 2
    # nothing of it is kept on the commit: the next loop builds its own
    # (what a commit does keep since ISSUE 42 is its own serialisation)
    c.sign_bytes("test-chain")(0)
    assert len(made) == 3
    assert set(vars(c)) - COMMIT_MEMOS == {"height", "round", "block_id", "signatures", "agg_sig"}


# -- the collect loops of types/validation -----------------------------------------


class StubVerifier:
    """In `_CommitVerifier`'s place: keeps what the funnel hands over."""

    made: list = []
    verdict = None  # None: all good; else the index (in add order) that fails

    def __init__(self, lane="live"):
        self.items = []
        self.via = "stub"
        StubVerifier.made.append(self)

    def add(self, pub_key, msg, sig):
        self.items.append((pub_key, msg, sig))

    def verify(self):
        bitmap = [i != StubVerifier.verdict for i in range(len(self.items))]
        return all(bitmap), bitmap


@pytest.fixture
def stub(monkeypatch):
    StubVerifier.made = []
    StubVerifier.verdict = None
    monkeypatch.setattr(validation, "_CommitVerifier", StubVerifier)
    return StubVerifier


def _signed_set(n, flags, tag, powers=None):
    """A validator set of `n` and a commit over it with seeded (not valid)
    signatures: the stub verifier decides, the funnel only collects."""
    vals, _ = tt.make_validator_set(n, seed=tag.encode())
    if powers:
        for v, p in zip(vals.validators, powers):
            v.voting_power = p
    rng = _rng(tag)
    sigs = []
    for i, v in enumerate(vals.validators):
        flag = flags[i % len(flags)]
        if flag == BLOCK_ID_FLAG_ABSENT:
            sigs.append(CommitSig())
        else:
            sigs.append(CommitSig(flag, v.address, T0 + rng.randrange(10**12),
                                  rng.randbytes(64)))
    return vals, Commit(7, 1, BID, tuple(sigs))


def _expected(chain_id, vals, commit, needed, count_all, by_index=True):
    """The per-index loop as it was: the triples it hands the verifier."""
    out, tallied, seen = [], 0, set()
    for idx, cs in enumerate(commit.signatures):
        if cs.flag == BLOCK_ID_FLAG_ABSENT:
            continue
        if by_index:
            val = vals.validators[idx]
        else:
            _, val = vals.get_by_address(cs.validator_address)
            if val is None:
                continue
            assert cs.validator_address not in seen
            seen.add(cs.validator_address)
        if not count_all and cs.flag != BLOCK_ID_FLAG_COMMIT:
            continue
        out.append((val.pub_key, ref_commit_vote_sign_bytes(commit, chain_id, idx),
                    cs.signature))
        if cs.flag == BLOCK_ID_FLAG_COMMIT:
            tallied += val.voting_power
        if not count_all and tallied > needed:
            break
    return out


FLAG_MIXES = {
    "all-commit": (BLOCK_ID_FLAG_COMMIT,),
    "absent-and-nil": (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL,
                       BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT,
                       BLOCK_ID_FLAG_ABSENT, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT),
    "nil-first": (BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT,
                  BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT),
}


@pytest.mark.parametrize("mix", FLAG_MIXES)
@pytest.mark.parametrize("entry", ["verify_commit", "verify_commit_light",
                                   "verify_commit_light_trusting", "verify_commit_range"])
def test_the_funnel_hands_over_the_per_index_sign_bytes(stub, entry, mix):
    chain_id = "collect-chain"
    vals, commit = _signed_set(30, FLAG_MIXES[mix], f"{entry}-{mix}")
    total = vals.total_voting_power()
    if entry == "verify_commit":
        validation.verify_commit(chain_id, vals, BID, 7, commit)
        want = _expected(chain_id, vals, commit, total * 2 // 3, count_all=True)
    elif entry == "verify_commit_light":
        validation.verify_commit_light(chain_id, vals, BID, 7, commit)
        want = _expected(chain_id, vals, commit, total * 2 // 3, count_all=False)
    elif entry == "verify_commit_light_trusting":
        # a trusted set that knows only two validators in three, in another order
        known = ValidatorSet([v for i, v in enumerate(vals.validators) if i % 3])
        validation.verify_commit_light_trusting(chain_id, known, commit)
        want = _expected(chain_id, known, commit, known.total_voting_power() // 3,
                            count_all=False, by_index=False)
    else:
        other_vals, other = _signed_set(30, FLAG_MIXES[mix], f"second-{mix}")
        validation.verify_commit_range(
            chain_id, [(vals, BID, 7, commit), (other_vals, BID, 7, other)])
        want = (_expected(chain_id, vals, commit, total * 2 // 3, False)
                + _expected(chain_id, other_vals, other,
                            other_vals.total_voting_power() * 2 // 3, False))
    assert len(stub.made) == 1
    got = stub.made[0].items
    assert len(got) == len(want) and got == want
    n_commits = 2 if entry == "verify_commit_range" else 1
    if entry != "verify_commit":  # light semantics stop at the quorum
        assert len(want) < 30 * n_commits


@pytest.mark.parametrize("entry", ["verify_commit", "verify_commit_light"])
def test_the_single_path_asks_for_the_same_triples(monkeypatch, entry):
    from tendermint_tpu.crypto import verify_hub

    asked = []
    monkeypatch.setattr(validation, "BATCH_VERIFY_THRESHOLD", 10**6)
    monkeypatch.setattr(verify_hub, "verify_one",
                        lambda pk, msg, sig, lane="live": asked.append((pk, msg, sig)) or True)
    vals, commit = _signed_set(12, FLAG_MIXES["absent-and-nil"], f"single-{entry}")
    getattr(validation, entry)("single-chain", vals, BID, 7, commit)
    want = _expected("single-chain", vals, commit,
                        vals.total_voting_power() * 2 // 3,
                        count_all=entry == "verify_commit")
    assert asked == want

    # and it stops at the first refused signature, naming it
    asked.clear()
    monkeypatch.setattr(verify_hub, "verify_one",
                        lambda pk, msg, sig, lane="live": asked.append(msg) or len(asked) < 4)
    fourth = next(i for i in range(12)
                  if ref_commit_vote_sign_bytes(commit, "single-chain", i) == want[3][1])
    assert fourth == (3 if entry == "verify_commit" else 4)  # light skips the nil vote
    with pytest.raises(validation.InvalidCommitError,
                       match=f"invalid signature at index {fourth}"):
        getattr(validation, entry)("single-chain", vals, BID, 7, commit)
    assert asked == [m for _, m, _ in want[:4]]


def test_the_aggregate_path_rebuilds_every_signers_message(monkeypatch):
    from tendermint_tpu.crypto import verify_hub

    vals, _ = tt.make_validator_set(5, key_types=("bls12381",))
    rng = _rng("agg-path")
    flags = (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_COMMIT,
             BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_COMMIT)
    sigs = tuple(CommitSig(f, v.address, T0 + rng.randrange(10**12), b"")
                 for f, v in zip(flags, vals.validators))
    commit = Commit(7, 1, BID, sigs, rng.randbytes(96))
    seen = []
    monkeypatch.setattr(verify_hub, "verify_aggregate",
                        lambda pubs, msgs, agg: seen.append((pubs, msgs, agg)) or True)
    validation.verify_commit("agg-chain", vals, BID, 7, commit)
    (pubs, msgs, agg), = seen
    assert pubs == [v.pub_key for v in vals.validators] and agg == commit.agg_sig
    assert msgs == [ref_commit_vote_sign_bytes(commit, "agg-chain", i) for i in range(5)]


def test_the_funnel_stops_and_fails_where_it_did(stub):
    chain_id = "errors-chain"
    # too little power for the block: 14 of 30 nil, 2 absent
    flags = (BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_COMMIT) * 14 + (BLOCK_ID_FLAG_ABSENT,) * 2
    vals, commit = _signed_set(30, flags, "short")
    needed = vals.total_voting_power() * 2 // 3
    for fn in (validation.verify_commit, validation.verify_commit_light):
        with pytest.raises(validation.InvalidCommitError) as e:
            fn(chain_id, vals, BID, 7, commit)
        assert str(e.value) == f"insufficient voting power: got 140, need > {needed}"
    with pytest.raises(validation.InvalidCommitError) as e:
        validation.verify_commit_range(chain_id, [(vals, BID, 7, commit)])
    assert str(e.value) == (
        f"insufficient voting power at height 7: got 140, need > {needed}")
    assert e.value.failed_index == 0
    assert not any(v.items and v.verify()[0] is False for v in stub.made)

    # a refused signature is named by its index in the COMMIT, not in the batch
    vals, commit = _signed_set(30, FLAG_MIXES["absent-and-nil"], "refused")
    stub.verdict = 7  # the eighth triple handed over; index 6 is absent
    want = _expected(chain_id, vals, commit, needed, count_all=True)
    eighth = next(i for i in range(30)
                  if ref_commit_vote_sign_bytes(commit, chain_id, i) == want[7][1])
    with pytest.raises(validation.InvalidCommitError) as e:
        validation.verify_commit(chain_id, vals, BID, 7, commit)
    assert str(e.value) == f"invalid signature at index {eighth}" and eighth == 8

    # the basic checks come first and build nothing
    stub.made.clear()
    with pytest.raises(validation.InvalidCommitError, match="commit height 7 != 8"):
        validation.verify_commit_light(chain_id, vals, BID, 8, commit)
    with pytest.raises(validation.InvalidCommitError, match="different block"):
        validation.verify_commit_range(chain_id, [(vals, NIL_BLOCK_ID, 7, commit)])
    assert stub.made == []


def test_collect_span_counts_one_template_a_commit(stub):
    """150 validators of equal power: 101 signatures pass 2/3, so a range
    collects 101 sign-bytes a commit from ONE template each."""
    old = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    try:
        entries = []
        for h in range(1, 6):
            vals, commit = _signed_set(150, FLAG_MIXES["all-commit"], f"range-{h}")
            entries.append((vals, BID, 7, commit))
        validation.verify_commit_range("range-chain", entries)
        rows = [s for s in trace.RECORDER.dump()
                if (s["subsystem"], s["name"]) == ("validation", "collect")]
    finally:
        trace.RECORDER.enabled = old
        trace.RECORDER.clear()
    (row,) = rows
    assert row["attrs"] == {"commits": 5, "sigs": 5 * 101, "templates": 5,
                            "edwards": 5 * 101, "host": 0}
    assert row["attrs"]["sigs"] == 101 * row["attrs"]["templates"]
    assert len(stub.made[0].items) == 5 * 101


# -- nothing is remembered between calls --------------------------------------------


def _light_block(n_vals=150) -> LightBlock:
    vals, _ = tt.make_validator_set(n_vals, seed=b"light-block")
    _, commit = _signed_set(n_vals, FLAG_MIXES["absent-and-nil"], "light-block")
    header = Header(chain_id="light-chain", height=7, time_ns=T0,
                    validators_hash=vals.hash(), next_validators_hash=vals.hash())
    return LightBlock(SignedHeader(header, commit), vals)


def test_a_light_block_keeps_its_commit_s_bytes_and_a_fresh_one_pays_again(monkeypatch):
    """Since ISSUE 42 a Commit OBJECT keeps its serialisation (a block's
    apply asks for it five times); nothing is keyed by value, so the next
    answer a provider sends — the same bytes, decoded into new objects —
    is serialised in full again, and a validator set keeps nothing."""
    lb = _light_block()
    raw = lb.encode()
    calls = {"sig": 0, "val": 0}
    real_sig, real_val = CommitSig.encode, Validator.encode

    def sig_encode(self):
        calls["sig"] += 1
        return real_sig(self)

    def val_encode(self):
        calls["val"] += 1
        return real_val(self)

    monkeypatch.setattr(CommitSig, "encode", sig_encode)
    monkeypatch.setattr(Validator, "encode", val_encode)

    def state(obj):
        return dict(vars(obj))

    store = TrustedStore()
    for _ in range(2):
        fresh = LightBlock.decode(raw)  # what a provider's answer decodes to
        commit = fresh.signed_header.commit
        before = (state(fresh), state(commit), state(fresh.validators),
                  [state(cs) for cs in commit.signatures],
                  [state(v) for v in fresh.validators.validators])
        per_save = []
        for _ in range(2):
            calls.update(sig=0, val=0)
            store.save(fresh)
            per_save.append(dict(calls))
        # the first save of a fresh answer pays for every element; the second
        # finds the commit's bytes kept, the set's encoded again
        assert per_save == [{"sig": 150, "val": 150}, {"sig": 0, "val": 150}]
        after = (state(fresh), state(commit), state(fresh.validators),
                 [state(cs) for cs in commit.signatures],
                 [state(v) for v in fresh.validators.validators])
        # nothing hung on any object but the commit's own serialisation
        kept = after[1]
        assert set(kept) - set(before[1]) <= COMMIT_MEMOS
        assert after[0] == before[0] and after[2:] == before[2:]
        assert {k: kept[k] for k in before[1]} == before[1]
        assert store.get(7).encode() == raw


def test_the_encoders_keep_no_cache_by_value():
    """Module state of the folded encoders is constants only: tags and the
    one-byte table. A cache keyed by timestamp, commit or validator would
    show up here as a dict, a set or an lru_cache wrapper."""
    for mod in (pe, canonical, block_mod, vs_mod):
        for name, value in vars(mod).items():
            if name.startswith("__"):
                continue
            assert not hasattr(value, "cache_info"), (mod.__name__, name)
            if isinstance(value, (dict, set, list)):
                assert name in ("_KIND_WIRE_TYPE",), (mod.__name__, name)
    assert pe._B1 == tuple(bytes((i,)) for i in range(128))
    assert all(t == ref_uvarint(i) for i, t in enumerate(pe._TAGS))
