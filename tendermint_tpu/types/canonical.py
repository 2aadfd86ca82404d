"""Canonical sign-bytes construction.

The bytes a validator signs must be identical across every implementation
that ever validates them, so they are built here with the deterministic
encoder and never from in-memory object reprs. Height and round are encoded
as sfixed64 (fixed width) — same rationale as the reference
(types/canonical.go:56): HSM signers do cross-height comparison on raw
bytes, so variable-length encodings are ruled out.

Layout (field numbers):

  CanonicalVote / CanonicalProposal:
    1: type (varint)         2: height (sfixed64)    3: round (sfixed64)
    4: block_id (msg)        5: pol_round (sfixed64, proposal only — shifts
                                vote field numbers by one: vote timestamp=5,
                                chain_id=6; proposal timestamp=6, chain_id=7)
  CanonicalBlockID:  1: hash (bytes)  2: part_set_header (msg)
  CanonicalPartSetHeader: 1: total (varint)  2: hash (bytes)
  Timestamp: 1: seconds (varint)  2: nanos (varint)

The result is length-prefixed (the signed message is the framed encoding).

Aggregate commits (types/block.py) deliberately do NOT introduce a new
canonical form: each signer of an aggregate commit signed exactly the
per-validator `vote_sign_bytes` above (distinct timestamps => distinct
messages), and the aggregate is a public point-sum of those signatures.
Keeping the sign-bytes identical across both commit wire forms is what
makes aggregation a pure data transformation — no re-signing, no HSM
changes, and the per-sig and aggregate verification paths accept
exactly the same signer statements.
"""

from __future__ import annotations

from collections.abc import Callable

from ..libs import protoenc as pe
from .keys import SignedMsgType

NANOS = 1_000_000_000

_TS_SECONDS = pe.field_tag(1, "varint")
_TS_NANOS = pe.field_tag(2, "varint")
_VOTE_TIMESTAMP = pe.field_tag(5, "message")


def encode_timestamp(ns: int) -> bytes:
    seconds, nanos = divmod(ns, NANOS)
    out = _TS_SECONDS + pe.varint(seconds) if seconds else b""
    if nanos:
        out += _TS_NANOS + pe.uvarint(nanos)
    return out


def encode_canonical_part_set_header(total: int, hash_: bytes) -> bytes:
    return pe.varint_field(1, total) + pe.bytes_field(2, hash_)


def encode_canonical_block_id(block_id) -> bytes | None:
    """None for nil/absent block IDs (field omitted entirely)."""
    if block_id is None or block_id.is_nil():
        return None
    return pe.bytes_field(1, block_id.hash) + pe.message_field(
        2,
        encode_canonical_part_set_header(
            block_id.part_set_header.total, block_id.part_set_header.hash
        ),
    )


def vote_sign_template(
    chain_id: str,
    msg_type: SignedMsgType,
    height: int,
    round_: int,
    block_id,
) -> Callable[[int], bytes]:
    """The canonical vote split into what a whole commit fixes — type,
    height, round, canonical block ID (omitted for nil) and chain ID —
    and what one signature adds: its timestamp field and the outer
    length prefix. The fixed part is encoded here, once; the function
    returned gives the sign-bytes of one timestamp. A 150-validator
    commit shares one template for its block votes and one for its nil
    votes; nothing is kept beyond the returned function."""
    head = pe.varint_field(1, int(msg_type))
    head += pe.sfixed64_field(2, height)
    head += pe.sfixed64_field(3, round_)
    cbid = encode_canonical_block_id(block_id)
    if cbid is not None:
        head += pe.message_field(4, cbid)
    head += _VOTE_TIMESTAMP  # a message field: emitted even when empty
    tail = pe.string_field(6, chain_id)
    fixed = len(head) + 1 + len(tail)
    uvarint = pe.uvarint

    def sign_bytes(timestamp_ns: int) -> bytes:
        ts = encode_timestamp(timestamp_ns)
        n = len(ts)
        return b"".join((uvarint(fixed + n), head, uvarint(n), ts, tail))

    return sign_bytes


def vote_sign_bytes(
    chain_id: str,
    msg_type: SignedMsgType,
    height: int,
    round_: int,
    block_id,
    timestamp_ns: int,
) -> bytes:
    return vote_sign_template(chain_id, msg_type, height, round_, block_id)(
        timestamp_ns
    )


def strip_timestamp(sign_bytes: bytes, field: int = 5) -> tuple[bytes, int]:
    """Canonical sign-bytes with the timestamp field removed (field 5 for
    votes, 6 for proposals); returns (stripped, timestamp_ns). Used by
    privval to allow re-signing messages that differ only in their
    timestamp (reference privval/file.go
    checkVotesOnlyDifferByTimestamp)."""
    r = pe.Reader(sign_bytes)
    inner = pe.Reader(r.read_bytes())  # drop the length prefix
    out = b""
    ts_ns = 0
    while not inner.eof():
        start = inner.pos
        f, wt = inner.read_tag()
        if f == field:
            tr = pe.Reader(inner.read_bytes())
            seconds = nanos = 0
            while not tr.eof():
                tf, twt = tr.read_tag()
                if tf == 1:
                    seconds = tr.read_uvarint()
                elif tf == 2:
                    nanos = tr.read_uvarint()
                else:
                    tr.skip(twt)
            ts_ns = seconds * NANOS + nanos
            continue
        inner.skip(wt)
        out += inner.data[start : inner.pos]
    return out, ts_ns


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id,
    timestamp_ns: int,
) -> bytes:
    out = pe.varint_field(1, int(SignedMsgType.PROPOSAL))
    out += pe.sfixed64_field(2, height)
    out += pe.sfixed64_field(3, round_)
    out += pe.sfixed64_field(4, pol_round if pol_round >= 0 else -1)
    cbid = encode_canonical_block_id(block_id)
    if cbid is not None:
        out += pe.message_field(5, cbid)
    out += pe.message_field(6, encode_timestamp(timestamp_ns))
    out += pe.string_field(7, chain_id)
    return pe.len_prefixed(out)
