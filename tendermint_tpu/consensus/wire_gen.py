# @generated by scripts/wiregen -- DO NOT EDIT BY HAND.
#
# Compiled from the blessed wire-schema lockfile
# (tendermint_tpu/tools/lint/wire_schema.lock.json) by
# tendermint_tpu/tools/wiregen. Regenerate with `scripts/wiregen
# --update`; verify freshness with `scripts/wiregen --check` or
# `scripts/tmtlint` (the wiregen-drift rule re-renders this module
# in memory and fails the gate on any byte difference). Disable at
# runtime with TMTPU_WIREGEN=0 (interpreted protoenc fallback).
# schema-hash: sha256:5b52fec663b9cc88d77ea13574ad2a4ddca8d60bfaf7577c642923375cde9b25
# tmtlint: allow-file[*] -- machine-generated codec; wiregen-drift pins it byte-identical to a fresh regen from the wire-schema lockfile
"""Generated hot-path consensus codec (see header; do not edit).

Bit-identical to the interpreted protoenc codec for every compiled
frame family: same bytes out of every encoder, same objects and the
same error classes/messages out of every decoder, including decode
bound rejections. Bounds (MAX_*) are read from the owning interpreted
modules at call time, so retuning or monkeypatching a bound governs
both codecs at once.
"""

import struct

from ..crypto import merkle as _mkl
from ..libs.bits import BitArray
from ..types import block as _blk
from ..types.block import (
    NIL_BLOCK_ID,
    BlockID,
    Commit,
    CommitSig,
    PartSetHeader,
)
from ..types.keys import BLOCK_ID_FLAG_ABSENT, SignedMsgType
from ..types.part_set import Part
from ..types.vote import Proposal, Vote
from . import messages as _msgs
from .messages import (
    BlockPartMessage,
    HasVoteBatchMessage,
    HasVoteMessage,
    NewRoundStepMessage,
    NewValidBlockMessage,
    ProposalMessage,
    ProposalPOLMessage,
    VoteBatchMessage,
    VoteMessage,
    VoteSetBitsMessage,
    VoteSetMaj23Message,
)

_Proof = _mkl.Proof
_new = object.__new__
_pq = struct.Struct("<q").pack
_uq = struct.Struct("<q").unpack_from
_B1 = tuple(bytes((i,)) for i in range(128))
_SMT = dict(SignedMsgType._value2member_map_)
_PSH0 = PartSetHeader()
_osa = object.__setattr__


def _uv(v):
    if v < 128:
        return _B1[v]
    out = bytearray()
    while v > 127:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _ev(v):
    if 0 <= v < 128:
        return _B1[v]
    if v < 0:
        v &= 0xFFFFFFFFFFFFFFFF
    out = bytearray()
    while v > 127:
        out.append((v & 0x7F) | 0x80)
        v >>= 7
    out.append(v)
    return bytes(out)


def _rv(buf, pos, end):
    result = 0
    shift = 0
    while True:
        if pos >= end:
            raise ValueError("truncated varint")
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7
        if shift > 70:
            raise ValueError("varint too long")


def _skip(buf, pos, end, wt):
    if wt == 0:
        return _rv(buf, pos, end)[1]
    if wt == 1:
        return pos + 8
    if wt == 2:
        n, pos = _rv(buf, pos, end)
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        return np
    if wt == 5:
        return pos + 4
    raise ValueError(f"unknown wire type {wt}")


def encode_timestamp(ns):
    seconds, nanos = divmod(ns, 1_000_000_000)
    if seconds:
        out = b"\x08" + _ev(seconds)
    else:
        out = b""
    if nanos:
        out += b"\x10" + _ev(nanos)
    return out


def encode_part_set_header(psh):
    total = psh.total
    if total:
        out = b"\x08" + _ev(total)
    else:
        out = b""
    h = psh.hash
    if h:
        out += b"\x12" + _uv(len(h)) + h
    return out


def encode_block_id(bid):
    h = bid.hash
    p = encode_part_set_header(bid.part_set_header)
    if h:
        return b"\x0a" + _uv(len(h)) + h + b"\x12" + _uv(len(p)) + p
    return b"\x12" + _uv(len(p)) + p


def encode_proof(p):
    total = p.total
    if total:
        out = [b"\x08" + _ev(total)]
    else:
        out = []
    i = p.index
    if i:
        out.append(b"\x10" + _ev(i))
    lh = p.leaf_hash
    if lh:
        out.append(b"\x1a" + _uv(len(lh)) + lh)
    for a in p.aunts:
        out.append(b"\x22" + _uv(len(a)) + a)
    return b"".join(out)


def encode_part(part):
    i = part.index + 1
    if i:
        out = b"\x08" + _ev(i)
    else:
        out = b""
    data = part.bytes_
    if data:
        out += b"\x12" + _uv(len(data)) + data
    pr = encode_proof(part.proof)
    return out + b"\x1a" + _uv(len(pr)) + pr


def encode_commit_sig(cs):
    fl = cs.flag
    if fl:
        out = b"\x08" + _ev(fl)
    else:
        out = b""
    a = cs.validator_address
    if a:
        out += b"\x12" + _uv(len(a)) + a
    ts = encode_timestamp(cs.timestamp_ns)
    out += b"\x1a" + _uv(len(ts)) + ts
    s = cs.signature
    if s:
        out += b"\x22" + _uv(len(s)) + s
    return out


def encode_commit(c):
    h = c.height
    if h:
        out = [b"\x09" + _pq(h)]
    else:
        out = []
    r = c.round
    if r:
        out.append(b"\x11" + _pq(r))
    bb = encode_block_id(c.block_id)
    out.append(b"\x1a" + _uv(len(bb)) + bb)
    ap = out.append
    for cs in c.signatures:
        e = encode_commit_sig(cs)
        ap(b"\x22" + _uv(len(e)) + e)
    a = c.agg_sig
    if a:
        out.append(b"\x2a" + _uv(len(a)) + a)
    return b"".join(out)


def encode_vote(v):
    tp = int(v.type)
    if tp:
        out = [b"\x08" + _ev(tp)]
    else:
        out = []
    h = v.height
    if h:
        out.append(b"\x11" + _pq(h))
    r = v.round
    if r:
        out.append(b"\x19" + _pq(r))
    bb = encode_block_id(v.block_id)
    out.append(b"\x22" + _uv(len(bb)) + bb)
    ts = encode_timestamp(v.timestamp_ns)
    out.append(b"\x2a" + _uv(len(ts)) + ts)
    a = v.validator_address
    if a:
        out.append(b"\x32" + _uv(len(a)) + a)
    i = v.validator_index + 1
    if i:
        out.append(b"\x38" + _ev(i))
    s = v.signature
    if s:
        out.append(b"\x42" + _uv(len(s)) + s)
    return b"".join(out)


def encode_proposal(p):
    h = p.height
    if h:
        out = [b"\x09" + _pq(h)]
    else:
        out = []
    r = p.round
    if r:
        out.append(b"\x11" + _pq(r))
    pol = p.pol_round if p.pol_round >= 0 else -1
    if pol:
        out.append(b"\x19" + _pq(pol))
    bb = encode_block_id(p.block_id)
    out.append(b"\x22" + _uv(len(bb)) + bb)
    ts = encode_timestamp(p.timestamp_ns)
    out.append(b"\x2a" + _uv(len(ts)) + ts)
    s = p.signature
    if s:
        out.append(b"\x32" + _uv(len(s)) + s)
    return b"".join(out)


def _e_bits(ba):
    n = len(ba)
    if n:
        out = b"\x08" + _ev(n)
    else:
        out = b""
    raw = ba.to_bytes()
    if raw:
        out += b"\x12" + _uv(len(raw)) + raw
    return out


def _e_has_vote(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.round
    if r:
        out += b"\x10" + _ev(r)
    tp = int(m.type)
    if tp:
        out += b"\x18" + _ev(tp)
    i = m.index + 1
    if i:
        out += b"\x20" + _ev(i)
    return out


def _e_nrs(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.round + 1
    if r:
        out += b"\x10" + _ev(r)
    s = m.step
    if s:
        out += b"\x18" + _ev(s)
    ss = m.seconds_since_start_time
    if ss:
        out += b"\x20" + _ev(ss)
    lc = m.last_commit_round + 1
    if lc:
        out += b"\x28" + _ev(lc)
    return b"\x0a" + _uv(len(out)) + out


def _e_nvb(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.round
    if r:
        out += b"\x10" + _ev(r)
    total, ph = m.block_part_set_header
    if total:
        psh = b"\x08" + _ev(total)
    else:
        psh = b""
    if ph:
        psh += b"\x12" + _uv(len(ph)) + ph
    out += b"\x1a" + _uv(len(psh)) + psh
    bb = _e_bits(m.block_parts)
    out += b"\x22" + _uv(len(bb)) + bb
    if m.is_commit:
        out += b"\x28\x01"
    return b"\x12" + _uv(len(out)) + out


def _e_prop(m):
    bb = encode_proposal(m.proposal)
    return b"\x1a" + _uv(len(bb)) + bb


def _e_pol(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.proposal_pol_round
    if r:
        out += b"\x10" + _ev(r)
    bb = _e_bits(m.proposal_pol)
    out += b"\x1a" + _uv(len(bb)) + bb
    return b"\x22" + _uv(len(out)) + out


def _e_bpart(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.round
    if r:
        out += b"\x10" + _ev(r)
    pb = encode_part(m.part)
    out += b"\x1a" + _uv(len(pb)) + pb
    return b"\x2a" + _uv(len(out)) + out


def _e_vote(m):
    bb = encode_vote(m.vote)
    return b"\x32" + _uv(len(bb)) + bb


def _e_hv(m):
    bb = _e_has_vote(m)
    return b"\x3a" + _uv(len(bb)) + bb


def _e_vsm(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.round
    if r:
        out += b"\x10" + _ev(r)
    tp = int(m.type)
    if tp:
        out += b"\x18" + _ev(tp)
    bb = encode_block_id(m.block_id)
    out += b"\x22" + _uv(len(bb)) + bb
    return b"\x42" + _uv(len(out)) + out


def _e_vsb(m):
    h = m.height
    if h:
        out = b"\x08" + _ev(h)
    else:
        out = b""
    r = m.round
    if r:
        out += b"\x10" + _ev(r)
    tp = int(m.type)
    if tp:
        out += b"\x18" + _ev(tp)
    bb = encode_block_id(m.block_id)
    out += b"\x22" + _uv(len(bb)) + bb
    vb = _e_bits(m.votes)
    out += b"\x2a" + _uv(len(vb)) + vb
    return b"\x4a" + _uv(len(out)) + out


def _e_vb(m):
    out = []
    ap = out.append
    for v in m.votes:
        bb = encode_vote(v)
        if bb:
            ap(b"\x0a" + _uv(len(bb)) + bb)
    body = b"".join(out)
    return b"\x52" + _uv(len(body)) + body


def _e_hvb(m):
    out = []
    ap = out.append
    for e in m.entries:
        bb = _e_has_vote(e)
        ap(b"\x0a" + _uv(len(bb)) + bb)
    body = b"".join(out)
    return b"\x5a" + _uv(len(body)) + body


def decode_timestamp(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    seconds = nanos = 0
    if pos < end and buf[pos] == 8:
        pos += 1
        seconds = buf[pos] if pos < end else 256
        if seconds < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            seconds = (seconds & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            seconds = (seconds & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            seconds = _r
    if pos < end and buf[pos] == 16:
        pos += 1
        nanos = buf[pos] if pos < end else 256
        if nanos < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            nanos = (nanos & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            nanos = (nanos & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            nanos = _r
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                seconds = buf[pos] if pos < end else 256
                if seconds < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    seconds = (seconds & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    seconds = (seconds & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    seconds = _r
            elif g == 2:
                nanos = buf[pos] if pos < end else 256
                if nanos < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    nanos = (nanos & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    nanos = (nanos & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    nanos = _r
            else:
                pos = _skip(buf, pos, end, tg & 7)
    return seconds * 1_000_000_000 + nanos


def decode_part_set_header(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    total = 0
    h = b""
    if pos < end and buf[pos] == 8:
        pos += 1
        total = buf[pos] if pos < end else 256
        if total < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            total = (total & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            total = (total & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            total = _r
    if pos < end and buf[pos] == 18:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        h = buf[pos:np]
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                total = buf[pos] if pos < end else 256
                if total < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    total = (total & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    total = (total & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    total = _r
            elif g == 2:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                h = buf[pos:np]
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    if not total and not h:
        return _PSH0
    m = _new(PartSetHeader)
    _osa(m, "__dict__", {"total": total, "hash": h})
    return m


def decode_block_id(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    h = b""
    psh = None
    if pos < end and buf[pos] == 10:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        h = buf[pos:np]
        pos = np
    if pos < end and buf[pos] == 18:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        psh = decode_part_set_header(buf, pos, np)
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                h = buf[pos:np]
                pos = np
            elif g == 2:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                psh = decode_part_set_header(buf, pos, np)
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    if psh is None:
        if not h:
            return NIL_BLOCK_ID
        psh = _PSH0
    m = _new(BlockID)
    _osa(m, "__dict__", {"hash": h, "part_set_header": psh})
    return m


def decode_proof(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    m_t = m_i = 0
    m_l = b""
    m_a = []
    if pos < end and buf[pos] == 8:
        pos += 1
        m_t = buf[pos] if pos < end else 256
        if m_t < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            m_t = (m_t & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            m_t = (m_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            m_t = _r
    if pos < end and buf[pos] == 16:
        pos += 1
        m_i = buf[pos] if pos < end else 256
        if m_i < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            m_i = (m_i & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            m_i = (m_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            m_i = _r
    if pos < end and buf[pos] == 26:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_l = buf[pos:np]
        pos = np
    if pos < end and buf[pos] == 34:
        _pap = m_a.append
        _pmx = _mkl.MAX_PROOF_AUNTS
    while pos < end and buf[pos] == 34:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        _pap(buf[pos:np])
        pos = np
        if len(m_a) > _pmx:
            raise ValueError(f"merkle proof aunts exceed {_pmx}")
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                m_t = buf[pos] if pos < end else 256
                if m_t < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    m_t = (m_t & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    m_t = (m_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    m_t = _r
            elif g == 2:
                m_i = buf[pos] if pos < end else 256
                if m_i < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    m_i = (m_i & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    m_i = (m_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    m_i = _r
            elif g == 3:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_l = buf[pos:np]
                pos = np
            elif g == 4:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_a.append(buf[pos:np])
                pos = np
                if len(m_a) > _mkl.MAX_PROOF_AUNTS:
                    raise ValueError(
                        f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                    )
            else:
                pos = _skip(buf, pos, end, tg & 7)
    m = _new(_Proof)
    _osa(m, "__dict__", {
        "total": m_t,
        "index": m_i,
        "leaf_hash": m_l,
        "aunts": m_a,
    })
    return m


def decode_part(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    m_i = 0
    m_d = b""
    m_p = None
    if pos < end and buf[pos] == 8:
        pos += 1
        v = buf[pos] if pos < end else 256
        if v < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            v = (v & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            v = _r
        m_i = v - 1
    if pos < end and buf[pos] == 18:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_d = buf[pos:np]
        pos = np
    if pos < end and buf[pos] == 26:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_p_t = m_p_i = 0
        m_p_l = b""
        m_p_a = []
        if pos < np and buf[pos] == 8:
            pos += 1
            m_p_t = buf[pos] if pos < np else 256
            if m_p_t < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                m_p_t = (m_p_t & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                m_p_t = (m_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                m_p_t = _r
        if pos < np and buf[pos] == 16:
            pos += 1
            m_p_i = buf[pos] if pos < np else 256
            if m_p_i < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                m_p_i = (m_p_i & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                m_p_i = (m_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                m_p_i = _r
        if pos < np and buf[pos] == 26:
            pos += 1
            n2 = buf[pos] if pos < np else 256
            if n2 < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n2 = _r
            np2 = pos + n2
            if np2 > np:
                raise ValueError("truncated bytes")
            m_p_l = buf[pos:np2]
            pos = np2
        if pos < np and buf[pos] == 34:
            _pap = m_p_a.append
            _pmx = _mkl.MAX_PROOF_AUNTS
        while pos < np and buf[pos] == 34:
            pos += 1
            n2 = buf[pos] if pos < np else 256
            if n2 < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n2 = _r
            np2 = pos + n2
            if np2 > np:
                raise ValueError("truncated bytes")
            _pap(buf[pos:np2])
            pos = np2
            if len(m_p_a) > _pmx:
                raise ValueError(f"merkle proof aunts exceed {_pmx}")
        if pos < np:
            while pos < np:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, np)
                g2 = tg >> 3
                if g2 == 1:
                    m_p_t = buf[pos] if pos < np else 256
                    if m_p_t < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_p_t = (m_p_t & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_p_t = (m_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_p_t = _r
                elif g2 == 2:
                    m_p_i = buf[pos] if pos < np else 256
                    if m_p_i < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_p_i = (m_p_i & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_p_i = (m_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_p_i = _r
                elif g2 == 3:
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_p_l = buf[pos:np2]
                    pos = np2
                elif g2 == 4:
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_p_a.append(buf[pos:np2])
                    pos = np2
                    if len(m_p_a) > _mkl.MAX_PROOF_AUNTS:
                        raise ValueError(
                            f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                        )
                else:
                    pos = _skip(buf, pos, np, tg & 7)
        m_p = _new(_Proof)
        _osa(m_p, "__dict__", {
            "total": m_p_t,
            "index": m_p_i,
            "leaf_hash": m_p_l,
            "aunts": m_p_a,
        })
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                v = buf[pos] if pos < end else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                m_i = v - 1
            elif g == 2:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_d = buf[pos:np]
                pos = np
            elif g == 3:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_p_t = m_p_i = 0
                m_p_l = b""
                m_p_a = []
                if pos < np and buf[pos] == 8:
                    pos += 1
                    m_p_t = buf[pos] if pos < np else 256
                    if m_p_t < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_p_t = (m_p_t & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_p_t = (m_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_p_t = _r
                if pos < np and buf[pos] == 16:
                    pos += 1
                    m_p_i = buf[pos] if pos < np else 256
                    if m_p_i < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_p_i = (m_p_i & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_p_i = (m_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_p_i = _r
                if pos < np and buf[pos] == 26:
                    pos += 1
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_p_l = buf[pos:np2]
                    pos = np2
                if pos < np and buf[pos] == 34:
                    _pap = m_p_a.append
                    _pmx = _mkl.MAX_PROOF_AUNTS
                while pos < np and buf[pos] == 34:
                    pos += 1
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    _pap(buf[pos:np2])
                    pos = np2
                    if len(m_p_a) > _pmx:
                        raise ValueError(f"merkle proof aunts exceed {_pmx}")
                if pos < np:
                    while pos < np:
                        tg = buf[pos]
                        if tg < 128:
                            pos += 1
                        else:
                            tg, pos = _rv(buf, pos, np)
                        g2 = tg >> 3
                        if g2 == 1:
                            m_p_t = buf[pos] if pos < np else 256
                            if m_p_t < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                m_p_t = (m_p_t & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                m_p_t = (m_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                m_p_t = _r
                        elif g2 == 2:
                            m_p_i = buf[pos] if pos < np else 256
                            if m_p_i < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                m_p_i = (m_p_i & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                m_p_i = (m_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                m_p_i = _r
                        elif g2 == 3:
                            n2 = buf[pos] if pos < np else 256
                            if n2 < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n2 = _r
                            np2 = pos + n2
                            if np2 > np:
                                raise ValueError("truncated bytes")
                            m_p_l = buf[pos:np2]
                            pos = np2
                        elif g2 == 4:
                            n2 = buf[pos] if pos < np else 256
                            if n2 < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n2 = _r
                            np2 = pos + n2
                            if np2 > np:
                                raise ValueError("truncated bytes")
                            m_p_a.append(buf[pos:np2])
                            pos = np2
                            if len(m_p_a) > _mkl.MAX_PROOF_AUNTS:
                                raise ValueError(
                                    f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                                )
                        else:
                            pos = _skip(buf, pos, np, tg & 7)
                m_p = _new(_Proof)
                _osa(m_p, "__dict__", {
                    "total": m_p_t,
                    "index": m_p_i,
                    "leaf_hash": m_p_l,
                    "aunts": m_p_a,
                })
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    if m_p is None:
        m_p = _new(_Proof)
        _osa(m_p, "__dict__", {"total": 0, "index": 0, "leaf_hash": b"", "aunts": []})
    m = _new(Part)
    _osa(m, "__dict__", {"index": m_i, "bytes_": m_d, "proof": m_p})
    return m


def decode_commit_sig(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    flag = BLOCK_ID_FLAG_ABSENT
    addr = b""
    ts = 0
    sig = b""
    if pos < end and buf[pos] == 8:
        pos += 1
        flag = buf[pos] if pos < end else 256
        if flag < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            flag = (flag & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            flag = (flag & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            flag = _r
    if pos < end and buf[pos] == 18:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        addr = buf[pos:np]
        pos = np
    if pos < end and buf[pos] == 26:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        ts = decode_timestamp(buf, pos, np)
        pos = np
    if pos < end and buf[pos] == 34:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        sig = buf[pos:np]
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                flag = buf[pos] if pos < end else 256
                if flag < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    flag = (flag & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    flag = (flag & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    flag = _r
            elif g == 2:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                addr = buf[pos:np]
                pos = np
            elif g == 3:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                ts = decode_timestamp(buf, pos, np)
                pos = np
            elif g == 4:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                sig = buf[pos:np]
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    m = _new(CommitSig)
    _osa(m, "__dict__", {
        "flag": flag,
        "validator_address": addr,
        "timestamp_ns": ts,
        "signature": sig,
    })
    return m


def decode_commit(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    height = round_ = 0
    bid = None
    sigs = []
    ap = sigs.append
    agg = b""
    mx = _blk.MAX_WIRE_COMMIT_SIGS
    if pos < end and buf[pos] == 9:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        height = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 17:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        round_ = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 26:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        bid = decode_block_id(buf, pos, np)
        pos = np
    while pos < end and buf[pos] == 34:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        ap(decode_commit_sig(buf, pos, np))
        pos = np
        if len(sigs) > mx:
            raise ValueError(f"commit signatures exceed {mx}")
    if pos < end and buf[pos] == 42:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        agg = buf[pos:np]
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                height = _uq(buf, pos)[0]
                pos += 8
            elif g == 2:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                round_ = _uq(buf, pos)[0]
                pos += 8
            elif g == 3:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                bid = decode_block_id(buf, pos, np)
                pos = np
            elif g == 4:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                ap(decode_commit_sig(buf, pos, np))
                pos = np
                if len(sigs) > mx:
                    raise ValueError(f"commit signatures exceed {mx}")
            elif g == 5:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                agg = buf[pos:np]
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    m = _new(Commit)
    _osa(m, "__dict__", {
        "height": height,
        "round": round_,
        "block_id": bid if bid is not None else NIL_BLOCK_ID,
        "signatures": tuple(sigs),
        "agg_sig": agg,
    })
    return m


def decode_vote(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    m_y = SignedMsgType.UNKNOWN
    m_e = m_r = 0
    m_b = None
    m_t = 0
    m_a = b""
    m_i = -1
    m_g = b""
    if pos < end and buf[pos] == 8:
        pos += 1
        v = buf[pos] if pos < end else 256
        if v < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            v = (v & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            v = _r
        m_y = _SMT.get(v)
        if m_y is None:
            m_y = SignedMsgType(v)
    if pos < end and buf[pos] == 17:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        m_e = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 25:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        m_r = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 34:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_b_h = b""
        m_b_p = None
        if pos < np and buf[pos] == 10:
            pos += 1
            n2 = buf[pos] if pos < np else 256
            if n2 < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n2 = _r
            np2 = pos + n2
            if np2 > np:
                raise ValueError("truncated bytes")
            m_b_h = buf[pos:np2]
            pos = np2
        if pos < np and buf[pos] == 18:
            pos += 1
            n2 = buf[pos] if pos < np else 256
            if n2 < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n2 = _r
            np2 = pos + n2
            if np2 > np:
                raise ValueError("truncated bytes")
            m_b_p_t = 0
            m_b_p_h = b""
            if pos < np2 and buf[pos] == 8:
                pos += 1
                m_b_p_t = buf[pos] if pos < np2 else 256
                if m_b_p_t < 128:
                    pos += 1
                elif pos + 1 < np2 and buf[pos + 1] < 128:
                    m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np2 and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np2:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    m_b_p_t = _r
            if pos < np2 and buf[pos] == 18:
                pos += 1
                n3 = buf[pos] if pos < np2 else 256
                if n3 < 128:
                    pos += 1
                elif pos + 1 < np2 and buf[pos + 1] < 128:
                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np2 and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np2:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n3 = _r
                np3 = pos + n3
                if np3 > np2:
                    raise ValueError("truncated bytes")
                m_b_p_h = buf[pos:np3]
                pos = np3
            if pos < np2:
                while pos < np2:
                    tg = buf[pos]
                    if tg < 128:
                        pos += 1
                    else:
                        tg, pos = _rv(buf, pos, np2)
                    g3 = tg >> 3
                    if g3 == 1:
                        m_b_p_t = buf[pos] if pos < np2 else 256
                        if m_b_p_t < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            m_b_p_t = _r
                    elif g3 == 2:
                        n3 = buf[pos] if pos < np2 else 256
                        if n3 < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n3 = _r
                        np3 = pos + n3
                        if np3 > np2:
                            raise ValueError("truncated bytes")
                        m_b_p_h = buf[pos:np3]
                        pos = np3
                    else:
                        pos = _skip(buf, pos, np2, tg & 7)
            if not m_b_p_t and not m_b_p_h:
                m_b_p = _PSH0
            else:
                m_b_p = _new(PartSetHeader)
                _osa(m_b_p, "__dict__", {"total": m_b_p_t, "hash": m_b_p_h})
            pos = np2
        if pos < np:
            while pos < np:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, np)
                g2 = tg >> 3
                if g2 == 1:
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_b_h = buf[pos:np2]
                    pos = np2
                elif g2 == 2:
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_b_p_t = 0
                    m_b_p_h = b""
                    if pos < np2 and buf[pos] == 8:
                        pos += 1
                        m_b_p_t = buf[pos] if pos < np2 else 256
                        if m_b_p_t < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            m_b_p_t = _r
                    if pos < np2 and buf[pos] == 18:
                        pos += 1
                        n3 = buf[pos] if pos < np2 else 256
                        if n3 < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n3 = _r
                        np3 = pos + n3
                        if np3 > np2:
                            raise ValueError("truncated bytes")
                        m_b_p_h = buf[pos:np3]
                        pos = np3
                    if pos < np2:
                        while pos < np2:
                            tg = buf[pos]
                            if tg < 128:
                                pos += 1
                            else:
                                tg, pos = _rv(buf, pos, np2)
                            g3 = tg >> 3
                            if g3 == 1:
                                m_b_p_t = buf[pos] if pos < np2 else 256
                                if m_b_p_t < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    m_b_p_t = _r
                            elif g3 == 2:
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                m_b_p_h = buf[pos:np3]
                                pos = np3
                            else:
                                pos = _skip(buf, pos, np2, tg & 7)
                    if not m_b_p_t and not m_b_p_h:
                        m_b_p = _PSH0
                    else:
                        m_b_p = _new(PartSetHeader)
                        _osa(m_b_p, "__dict__", {"total": m_b_p_t, "hash": m_b_p_h})
                    pos = np2
                else:
                    pos = _skip(buf, pos, np, tg & 7)
        if m_b_p is None and not m_b_h:
            m_b = NIL_BLOCK_ID
        else:
            m_b = _new(BlockID)
            _osa(m_b, "__dict__", {
                "hash": m_b_h,
                "part_set_header": m_b_p if m_b_p is not None else _PSH0,
            })
        pos = np
    if pos < end and buf[pos] == 42:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_t_s = m_t_n = 0
        if pos < np and buf[pos] == 8:
            pos += 1
            m_t_s = buf[pos] if pos < np else 256
            if m_t_s < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                m_t_s = (m_t_s & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                m_t_s = (m_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                m_t_s = _r
        if pos < np and buf[pos] == 16:
            pos += 1
            m_t_n = buf[pos] if pos < np else 256
            if m_t_n < 128:
                pos += 1
            elif pos + 1 < np and buf[pos + 1] < 128:
                m_t_n = (m_t_n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < np and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                m_t_n = (m_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= np:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                m_t_n = _r
        if pos < np:
            while pos < np:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, np)
                g2 = tg >> 3
                if g2 == 1:
                    m_t_s = buf[pos] if pos < np else 256
                    if m_t_s < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_t_s = (m_t_s & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_t_s = (m_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_t_s = _r
                elif g2 == 2:
                    m_t_n = buf[pos] if pos < np else 256
                    if m_t_n < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_t_n = (m_t_n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_t_n = (m_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_t_n = _r
                else:
                    pos = _skip(buf, pos, np, tg & 7)
        m_t = m_t_s * 1_000_000_000 + m_t_n
        pos = np
    if pos < end and buf[pos] == 50:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_a = buf[pos:np]
        pos = np
    if pos < end and buf[pos] == 56:
        pos += 1
        v = buf[pos] if pos < end else 256
        if v < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            v = (v & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            v = _r
        m_i = v - 1
    if pos < end and buf[pos] == 66:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        m_g = buf[pos:np]
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                v = buf[pos] if pos < end else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                m_y = _SMT.get(v)
                if m_y is None:
                    m_y = SignedMsgType(v)
            elif g == 2:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                m_e = _uq(buf, pos)[0]
                pos += 8
            elif g == 3:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                m_r = _uq(buf, pos)[0]
                pos += 8
            elif g == 4:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_b_h = b""
                m_b_p = None
                if pos < np and buf[pos] == 10:
                    pos += 1
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_b_h = buf[pos:np2]
                    pos = np2
                if pos < np and buf[pos] == 18:
                    pos += 1
                    n2 = buf[pos] if pos < np else 256
                    if n2 < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n2 = _r
                    np2 = pos + n2
                    if np2 > np:
                        raise ValueError("truncated bytes")
                    m_b_p_t = 0
                    m_b_p_h = b""
                    if pos < np2 and buf[pos] == 8:
                        pos += 1
                        m_b_p_t = buf[pos] if pos < np2 else 256
                        if m_b_p_t < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            m_b_p_t = _r
                    if pos < np2 and buf[pos] == 18:
                        pos += 1
                        n3 = buf[pos] if pos < np2 else 256
                        if n3 < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n3 = _r
                        np3 = pos + n3
                        if np3 > np2:
                            raise ValueError("truncated bytes")
                        m_b_p_h = buf[pos:np3]
                        pos = np3
                    if pos < np2:
                        while pos < np2:
                            tg = buf[pos]
                            if tg < 128:
                                pos += 1
                            else:
                                tg, pos = _rv(buf, pos, np2)
                            g3 = tg >> 3
                            if g3 == 1:
                                m_b_p_t = buf[pos] if pos < np2 else 256
                                if m_b_p_t < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    m_b_p_t = _r
                            elif g3 == 2:
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                m_b_p_h = buf[pos:np3]
                                pos = np3
                            else:
                                pos = _skip(buf, pos, np2, tg & 7)
                    if not m_b_p_t and not m_b_p_h:
                        m_b_p = _PSH0
                    else:
                        m_b_p = _new(PartSetHeader)
                        _osa(m_b_p, "__dict__", {"total": m_b_p_t, "hash": m_b_p_h})
                    pos = np2
                if pos < np:
                    while pos < np:
                        tg = buf[pos]
                        if tg < 128:
                            pos += 1
                        else:
                            tg, pos = _rv(buf, pos, np)
                        g2 = tg >> 3
                        if g2 == 1:
                            n2 = buf[pos] if pos < np else 256
                            if n2 < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n2 = _r
                            np2 = pos + n2
                            if np2 > np:
                                raise ValueError("truncated bytes")
                            m_b_h = buf[pos:np2]
                            pos = np2
                        elif g2 == 2:
                            n2 = buf[pos] if pos < np else 256
                            if n2 < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n2 = _r
                            np2 = pos + n2
                            if np2 > np:
                                raise ValueError("truncated bytes")
                            m_b_p_t = 0
                            m_b_p_h = b""
                            if pos < np2 and buf[pos] == 8:
                                pos += 1
                                m_b_p_t = buf[pos] if pos < np2 else 256
                                if m_b_p_t < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    m_b_p_t = _r
                            if pos < np2 and buf[pos] == 18:
                                pos += 1
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                m_b_p_h = buf[pos:np3]
                                pos = np3
                            if pos < np2:
                                while pos < np2:
                                    tg = buf[pos]
                                    if tg < 128:
                                        pos += 1
                                    else:
                                        tg, pos = _rv(buf, pos, np2)
                                    g3 = tg >> 3
                                    if g3 == 1:
                                        m_b_p_t = buf[pos] if pos < np2 else 256
                                        if m_b_p_t < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            m_b_p_t = (m_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            m_b_p_t = (m_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            m_b_p_t = _r
                                    elif g3 == 2:
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        m_b_p_h = buf[pos:np3]
                                        pos = np3
                                    else:
                                        pos = _skip(buf, pos, np2, tg & 7)
                            if not m_b_p_t and not m_b_p_h:
                                m_b_p = _PSH0
                            else:
                                m_b_p = _new(PartSetHeader)
                                _osa(m_b_p, "__dict__", {"total": m_b_p_t, "hash": m_b_p_h})
                            pos = np2
                        else:
                            pos = _skip(buf, pos, np, tg & 7)
                if m_b_p is None and not m_b_h:
                    m_b = NIL_BLOCK_ID
                else:
                    m_b = _new(BlockID)
                    _osa(m_b, "__dict__", {
                        "hash": m_b_h,
                        "part_set_header": m_b_p if m_b_p is not None else _PSH0,
                    })
                pos = np
            elif g == 5:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_t_s = m_t_n = 0
                if pos < np and buf[pos] == 8:
                    pos += 1
                    m_t_s = buf[pos] if pos < np else 256
                    if m_t_s < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_t_s = (m_t_s & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_t_s = (m_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_t_s = _r
                if pos < np and buf[pos] == 16:
                    pos += 1
                    m_t_n = buf[pos] if pos < np else 256
                    if m_t_n < 128:
                        pos += 1
                    elif pos + 1 < np and buf[pos + 1] < 128:
                        m_t_n = (m_t_n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        m_t_n = (m_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        m_t_n = _r
                if pos < np:
                    while pos < np:
                        tg = buf[pos]
                        if tg < 128:
                            pos += 1
                        else:
                            tg, pos = _rv(buf, pos, np)
                        g2 = tg >> 3
                        if g2 == 1:
                            m_t_s = buf[pos] if pos < np else 256
                            if m_t_s < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                m_t_s = (m_t_s & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                m_t_s = (m_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                m_t_s = _r
                        elif g2 == 2:
                            m_t_n = buf[pos] if pos < np else 256
                            if m_t_n < 128:
                                pos += 1
                            elif pos + 1 < np and buf[pos + 1] < 128:
                                m_t_n = (m_t_n & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                m_t_n = (m_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                m_t_n = _r
                        else:
                            pos = _skip(buf, pos, np, tg & 7)
                m_t = m_t_s * 1_000_000_000 + m_t_n
                pos = np
            elif g == 6:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_a = buf[pos:np]
                pos = np
            elif g == 7:
                v = buf[pos] if pos < end else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                m_i = v - 1
            elif g == 8:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                m_g = buf[pos:np]
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    m = _new(Vote)
    _osa(m, "__dict__", {
        "type": m_y,
        "height": m_e,
        "round": m_r,
        "block_id": m_b if m_b is not None else NIL_BLOCK_ID,
        "timestamp_ns": m_t,
        "validator_address": m_a,
        "validator_index": m_i,
        "signature": m_g,
    })
    return m


def decode_proposal(buf, pos=0, end=None):
    if end is None:
        end = len(buf)
    height = round_ = 0
    pol = -1
    bid = None
    ts = 0
    sig = b""
    if pos < end and buf[pos] == 9:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        height = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 17:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        round_ = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 25:
        pos += 1
        if pos + 8 > end:
            raise ValueError("truncated sfixed64")
        pol = _uq(buf, pos)[0]
        pos += 8
    if pos < end and buf[pos] == 34:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        bid = decode_block_id(buf, pos, np)
        pos = np
    if pos < end and buf[pos] == 42:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        ts = decode_timestamp(buf, pos, np)
        pos = np
    if pos < end and buf[pos] == 50:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
        np = pos + n
        if np > end:
            raise ValueError("truncated bytes")
        sig = buf[pos:np]
        pos = np
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                height = _uq(buf, pos)[0]
                pos += 8
            elif g == 2:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                round_ = _uq(buf, pos)[0]
                pos += 8
            elif g == 3:
                if pos + 8 > end:
                    raise ValueError("truncated sfixed64")
                pol = _uq(buf, pos)[0]
                pos += 8
            elif g == 4:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                bid = decode_block_id(buf, pos, np)
                pos = np
            elif g == 5:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                ts = decode_timestamp(buf, pos, np)
                pos = np
            elif g == 6:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
                np = pos + n
                if np > end:
                    raise ValueError("truncated bytes")
                sig = buf[pos:np]
                pos = np
            else:
                pos = _skip(buf, pos, end, tg & 7)
    m = _new(Proposal)
    _osa(m, "__dict__", {
        "height": height,
        "round": round_,
        "pol_round": pol,
        "block_id": bid if bid is not None else NIL_BLOCK_ID,
        "timestamp_ns": ts,
        "signature": sig,
    })
    return m


def _d_bits(buf, pos, end):
    n = 0
    raw = b""
    if pos < end and buf[pos] == 8:
        pos += 1
        n = buf[pos] if pos < end else 256
        if n < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n = (n & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n = _r
    if pos < end and buf[pos] == 18:
        pos += 1
        n2 = buf[pos] if pos < end else 256
        if n2 < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            n2 = _r
        np2 = pos + n2
        if np2 > end:
            raise ValueError("truncated bytes")
        raw = buf[pos:np2]
        pos = np2
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                n = buf[pos] if pos < end else 256
                if n < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n = (n & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n = _r
            elif g == 2:
                n2 = buf[pos] if pos < end else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > end:
                    raise ValueError("truncated bytes")
                raw = buf[pos:np2]
                pos = np2
            else:
                pos = _skip(buf, pos, end, tg & 7)
    mx = _msgs.MAX_WIRE_BITS
    if n > mx:
        raise ValueError(f"wire bit array of {n} bits exceeds {mx}")
    return BitArray.from_bytes(n, raw)


def _d_has_vote(buf, pos, end):
    height = round_ = 0
    type_ = SignedMsgType.UNKNOWN
    idx = -1
    if pos < end and buf[pos] == 8:
        pos += 1
        height = buf[pos] if pos < end else 256
        if height < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            height = (height & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            height = _r
    if pos < end and buf[pos] == 16:
        pos += 1
        round_ = buf[pos] if pos < end else 256
        if round_ < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            round_ = _r
    if pos < end and buf[pos] == 24:
        pos += 1
        v = buf[pos] if pos < end else 256
        if v < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            v = (v & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            v = _r
        type_ = _SMT.get(v)
        if type_ is None:
            type_ = SignedMsgType(v)
    if pos < end and buf[pos] == 32:
        pos += 1
        v = buf[pos] if pos < end else 256
        if v < 128:
            pos += 1
        elif pos + 1 < end and buf[pos + 1] < 128:
            v = (v & 0x7F) | (buf[pos + 1] << 7)
            pos += 2
        elif pos + 2 < end and buf[pos + 2] < 128:
            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
            pos += 3
        else:
            _r = 0
            _s = 0
            while True:
                if pos >= end:
                    raise ValueError("truncated varint")
                _b = buf[pos]
                pos += 1
                _r |= (_b & 0x7F) << _s
                if not _b & 0x80:
                    break
                _s += 7
                if _s > 70:
                    raise ValueError("varint too long")
            v = _r
        idx = v - 1
    if pos < end:
        while pos < end:
            tg = buf[pos]
            if tg < 128:
                pos += 1
            else:
                tg, pos = _rv(buf, pos, end)
            g = tg >> 3
            if g == 1:
                height = buf[pos] if pos < end else 256
                if height < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    height = (height & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    height = _r
            elif g == 2:
                round_ = buf[pos] if pos < end else 256
                if round_ < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    round_ = _r
            elif g == 3:
                v = buf[pos] if pos < end else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                type_ = _SMT.get(v)
                if type_ is None:
                    type_ = SignedMsgType(v)
            elif g == 4:
                v = buf[pos] if pos < end else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < end and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < end and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= end:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                idx = v - 1
            else:
                pos = _skip(buf, pos, end, tg & 7)
    mx = _msgs.MAX_WIRE_INDEX
    if idx > mx:
        raise ValueError(f"has-vote index {idx} exceeds {mx}")
    m = _new(HasVoteMessage)
    _osa(m, "__dict__", {"height": height, "round": round_, "type": type_, "index": idx})
    return m


def decode_message(data):
    buf = data
    end = len(buf)
    tg = buf[0] if end else 256
    if tg < 128:
        pos = 1
    else:
        tg, pos = _rv(buf, 0, end)
    f = tg >> 3
    n = buf[pos] if pos < end else 256
    if n < 128:
        pos += 1
    elif pos + 1 < end and buf[pos + 1] < 128:
        n = (n & 0x7F) | (buf[pos + 1] << 7)
        pos += 2
    elif pos + 2 < end and buf[pos + 2] < 128:
        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
        pos += 3
    else:
        _r = 0
        _s = 0
        while True:
            if pos >= end:
                raise ValueError("truncated varint")
            _b = buf[pos]
            pos += 1
            _r |= (_b & 0x7F) << _s
            if not _b & 0x80:
                break
            _s += 7
            if _s > 70:
                raise ValueError("varint too long")
        n = _r
    bend = pos + n
    if bend > end:
        raise ValueError("truncated bytes")
    if f == 10:
        votes = []
        ap = votes.append
        mx = _msgs.MAX_BATCH_VOTES
        _bm = {}
        while pos < bend and buf[pos] == 10:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            vt_y = SignedMsgType.UNKNOWN
            vt_e = vt_r = 0
            vt_b = None
            vt_t = 0
            vt_a = b""
            vt_i = -1
            vt_g = b""
            if pos < np and buf[pos] == 8:
                pos += 1
                v = buf[pos] if pos < np else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                vt_y = _SMT.get(v)
                if vt_y is None:
                    vt_y = SignedMsgType(v)
            if pos < np and buf[pos] == 17:
                pos += 1
                if pos + 8 > np:
                    raise ValueError("truncated sfixed64")
                vt_e = _uq(buf, pos)[0]
                pos += 8
            if pos < np and buf[pos] == 25:
                pos += 1
                if pos + 8 > np:
                    raise ValueError("truncated sfixed64")
                vt_r = _uq(buf, pos)[0]
                pos += 8
            if pos < np and buf[pos] == 34:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                _k = buf[pos:np2]
                vt_b = _bm.get(_k)
                if vt_b is None:
                    vt_b_h = b""
                    vt_b_p = None
                    if pos < np2 and buf[pos] == 10:
                        pos += 1
                        n3 = buf[pos] if pos < np2 else 256
                        if n3 < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n3 = _r
                        np3 = pos + n3
                        if np3 > np2:
                            raise ValueError("truncated bytes")
                        vt_b_h = buf[pos:np3]
                        pos = np3
                    if pos < np2 and buf[pos] == 18:
                        pos += 1
                        n3 = buf[pos] if pos < np2 else 256
                        if n3 < 128:
                            pos += 1
                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np2:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n3 = _r
                        np3 = pos + n3
                        if np3 > np2:
                            raise ValueError("truncated bytes")
                        vt_b_p_t = 0
                        vt_b_p_h = b""
                        if pos < np3 and buf[pos] == 8:
                            pos += 1
                            vt_b_p_t = buf[pos] if pos < np3 else 256
                            if vt_b_p_t < 128:
                                pos += 1
                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np3:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_b_p_t = _r
                        if pos < np3 and buf[pos] == 18:
                            pos += 1
                            n4 = buf[pos] if pos < np3 else 256
                            if n4 < 128:
                                pos += 1
                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np3:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n4 = _r
                            np4 = pos + n4
                            if np4 > np3:
                                raise ValueError("truncated bytes")
                            vt_b_p_h = buf[pos:np4]
                            pos = np4
                        if pos < np3:
                            while pos < np3:
                                tg = buf[pos]
                                if tg < 128:
                                    pos += 1
                                else:
                                    tg, pos = _rv(buf, pos, np3)
                                g4 = tg >> 3
                                if g4 == 1:
                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                    if vt_b_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_b_p_t = _r
                                elif g4 == 2:
                                    n4 = buf[pos] if pos < np3 else 256
                                    if n4 < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n4 = _r
                                    np4 = pos + n4
                                    if np4 > np3:
                                        raise ValueError("truncated bytes")
                                    vt_b_p_h = buf[pos:np4]
                                    pos = np4
                                else:
                                    pos = _skip(buf, pos, np3, tg & 7)
                        if not vt_b_p_t and not vt_b_p_h:
                            vt_b_p = _PSH0
                        else:
                            vt_b_p = _new(PartSetHeader)
                            _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                        pos = np3
                    if pos < np2:
                        while pos < np2:
                            tg = buf[pos]
                            if tg < 128:
                                pos += 1
                            else:
                                tg, pos = _rv(buf, pos, np2)
                            g3 = tg >> 3
                            if g3 == 1:
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                vt_b_h = buf[pos:np3]
                                pos = np3
                            elif g3 == 2:
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                vt_b_p_t = 0
                                vt_b_p_h = b""
                                if pos < np3 and buf[pos] == 8:
                                    pos += 1
                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                    if vt_b_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_b_p_t = _r
                                if pos < np3 and buf[pos] == 18:
                                    pos += 1
                                    n4 = buf[pos] if pos < np3 else 256
                                    if n4 < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n4 = _r
                                    np4 = pos + n4
                                    if np4 > np3:
                                        raise ValueError("truncated bytes")
                                    vt_b_p_h = buf[pos:np4]
                                    pos = np4
                                if pos < np3:
                                    while pos < np3:
                                        tg = buf[pos]
                                        if tg < 128:
                                            pos += 1
                                        else:
                                            tg, pos = _rv(buf, pos, np3)
                                        g4 = tg >> 3
                                        if g4 == 1:
                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                            if vt_b_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_b_p_t = _r
                                        elif g4 == 2:
                                            n4 = buf[pos] if pos < np3 else 256
                                            if n4 < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n4 = _r
                                            np4 = pos + n4
                                            if np4 > np3:
                                                raise ValueError("truncated bytes")
                                            vt_b_p_h = buf[pos:np4]
                                            pos = np4
                                        else:
                                            pos = _skip(buf, pos, np3, tg & 7)
                                if not vt_b_p_t and not vt_b_p_h:
                                    vt_b_p = _PSH0
                                else:
                                    vt_b_p = _new(PartSetHeader)
                                    _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                pos = np3
                            else:
                                pos = _skip(buf, pos, np2, tg & 7)
                    if vt_b_p is None and not vt_b_h:
                        vt_b = NIL_BLOCK_ID
                    else:
                        vt_b = _new(BlockID)
                        _osa(vt_b, "__dict__", {
                            "hash": vt_b_h,
                            "part_set_header": vt_b_p if vt_b_p is not None else _PSH0,
                        })
                    _bm[_k] = vt_b
                pos = np2
            if pos < np and buf[pos] == 42:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                vt_t_s = vt_t_n = 0
                if pos < np2 and buf[pos] == 8:
                    pos += 1
                    vt_t_s = buf[pos] if pos < np2 else 256
                    if vt_t_s < 128:
                        pos += 1
                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                        vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np2:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        vt_t_s = _r
                if pos < np2 and buf[pos] == 16:
                    pos += 1
                    vt_t_n = buf[pos] if pos < np2 else 256
                    if vt_t_n < 128:
                        pos += 1
                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                        vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np2:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        vt_t_n = _r
                if pos < np2:
                    while pos < np2:
                        tg = buf[pos]
                        if tg < 128:
                            pos += 1
                        else:
                            tg, pos = _rv(buf, pos, np2)
                        g3 = tg >> 3
                        if g3 == 1:
                            vt_t_s = buf[pos] if pos < np2 else 256
                            if vt_t_s < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_t_s = _r
                        elif g3 == 2:
                            vt_t_n = buf[pos] if pos < np2 else 256
                            if vt_t_n < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_t_n = _r
                        else:
                            pos = _skip(buf, pos, np2, tg & 7)
                vt_t = vt_t_s * 1_000_000_000 + vt_t_n
                pos = np2
            if pos < np and buf[pos] == 50:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                vt_a = buf[pos:np2]
                pos = np2
            if pos < np and buf[pos] == 56:
                pos += 1
                v = buf[pos] if pos < np else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                vt_i = v - 1
            if pos < np and buf[pos] == 66:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                vt_g = buf[pos:np2]
                pos = np2
            if pos < np:
                while pos < np:
                    tg = buf[pos]
                    if tg < 128:
                        pos += 1
                    else:
                        tg, pos = _rv(buf, pos, np)
                    g2 = tg >> 3
                    if g2 == 1:
                        v = buf[pos] if pos < np else 256
                        if v < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            v = (v & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            v = _r
                        vt_y = _SMT.get(v)
                        if vt_y is None:
                            vt_y = SignedMsgType(v)
                    elif g2 == 2:
                        if pos + 8 > np:
                            raise ValueError("truncated sfixed64")
                        vt_e = _uq(buf, pos)[0]
                        pos += 8
                    elif g2 == 3:
                        if pos + 8 > np:
                            raise ValueError("truncated sfixed64")
                        vt_r = _uq(buf, pos)[0]
                        pos += 8
                    elif g2 == 4:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        _k = buf[pos:np2]
                        vt_b = _bm.get(_k)
                        if vt_b is None:
                            vt_b_h = b""
                            vt_b_p = None
                            if pos < np2 and buf[pos] == 10:
                                pos += 1
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                vt_b_h = buf[pos:np3]
                                pos = np3
                            if pos < np2 and buf[pos] == 18:
                                pos += 1
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                vt_b_p_t = 0
                                vt_b_p_h = b""
                                if pos < np3 and buf[pos] == 8:
                                    pos += 1
                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                    if vt_b_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_b_p_t = _r
                                if pos < np3 and buf[pos] == 18:
                                    pos += 1
                                    n4 = buf[pos] if pos < np3 else 256
                                    if n4 < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n4 = _r
                                    np4 = pos + n4
                                    if np4 > np3:
                                        raise ValueError("truncated bytes")
                                    vt_b_p_h = buf[pos:np4]
                                    pos = np4
                                if pos < np3:
                                    while pos < np3:
                                        tg = buf[pos]
                                        if tg < 128:
                                            pos += 1
                                        else:
                                            tg, pos = _rv(buf, pos, np3)
                                        g4 = tg >> 3
                                        if g4 == 1:
                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                            if vt_b_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_b_p_t = _r
                                        elif g4 == 2:
                                            n4 = buf[pos] if pos < np3 else 256
                                            if n4 < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n4 = _r
                                            np4 = pos + n4
                                            if np4 > np3:
                                                raise ValueError("truncated bytes")
                                            vt_b_p_h = buf[pos:np4]
                                            pos = np4
                                        else:
                                            pos = _skip(buf, pos, np3, tg & 7)
                                if not vt_b_p_t and not vt_b_p_h:
                                    vt_b_p = _PSH0
                                else:
                                    vt_b_p = _new(PartSetHeader)
                                    _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                pos = np3
                            if pos < np2:
                                while pos < np2:
                                    tg = buf[pos]
                                    if tg < 128:
                                        pos += 1
                                    else:
                                        tg, pos = _rv(buf, pos, np2)
                                    g3 = tg >> 3
                                    if g3 == 1:
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        vt_b_h = buf[pos:np3]
                                        pos = np3
                                    elif g3 == 2:
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        vt_b_p_t = 0
                                        vt_b_p_h = b""
                                        if pos < np3 and buf[pos] == 8:
                                            pos += 1
                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                            if vt_b_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_b_p_t = _r
                                        if pos < np3 and buf[pos] == 18:
                                            pos += 1
                                            n4 = buf[pos] if pos < np3 else 256
                                            if n4 < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n4 = _r
                                            np4 = pos + n4
                                            if np4 > np3:
                                                raise ValueError("truncated bytes")
                                            vt_b_p_h = buf[pos:np4]
                                            pos = np4
                                        if pos < np3:
                                            while pos < np3:
                                                tg = buf[pos]
                                                if tg < 128:
                                                    pos += 1
                                                else:
                                                    tg, pos = _rv(buf, pos, np3)
                                                g4 = tg >> 3
                                                if g4 == 1:
                                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                                    if vt_b_p_t < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        vt_b_p_t = _r
                                                elif g4 == 2:
                                                    n4 = buf[pos] if pos < np3 else 256
                                                    if n4 < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        n4 = _r
                                                    np4 = pos + n4
                                                    if np4 > np3:
                                                        raise ValueError("truncated bytes")
                                                    vt_b_p_h = buf[pos:np4]
                                                    pos = np4
                                                else:
                                                    pos = _skip(buf, pos, np3, tg & 7)
                                        if not vt_b_p_t and not vt_b_p_h:
                                            vt_b_p = _PSH0
                                        else:
                                            vt_b_p = _new(PartSetHeader)
                                            _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                        pos = np3
                                    else:
                                        pos = _skip(buf, pos, np2, tg & 7)
                            if vt_b_p is None and not vt_b_h:
                                vt_b = NIL_BLOCK_ID
                            else:
                                vt_b = _new(BlockID)
                                _osa(vt_b, "__dict__", {
                                    "hash": vt_b_h,
                                    "part_set_header": vt_b_p if vt_b_p is not None else _PSH0,
                                })
                            _bm[_k] = vt_b
                        pos = np2
                    elif g2 == 5:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        vt_t_s = vt_t_n = 0
                        if pos < np2 and buf[pos] == 8:
                            pos += 1
                            vt_t_s = buf[pos] if pos < np2 else 256
                            if vt_t_s < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_t_s = _r
                        if pos < np2 and buf[pos] == 16:
                            pos += 1
                            vt_t_n = buf[pos] if pos < np2 else 256
                            if vt_t_n < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_t_n = _r
                        if pos < np2:
                            while pos < np2:
                                tg = buf[pos]
                                if tg < 128:
                                    pos += 1
                                else:
                                    tg, pos = _rv(buf, pos, np2)
                                g3 = tg >> 3
                                if g3 == 1:
                                    vt_t_s = buf[pos] if pos < np2 else 256
                                    if vt_t_s < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_t_s = _r
                                elif g3 == 2:
                                    vt_t_n = buf[pos] if pos < np2 else 256
                                    if vt_t_n < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_t_n = _r
                                else:
                                    pos = _skip(buf, pos, np2, tg & 7)
                        vt_t = vt_t_s * 1_000_000_000 + vt_t_n
                        pos = np2
                    elif g2 == 6:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        vt_a = buf[pos:np2]
                        pos = np2
                    elif g2 == 7:
                        v = buf[pos] if pos < np else 256
                        if v < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            v = (v & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            v = _r
                        vt_i = v - 1
                    elif g2 == 8:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        vt_g = buf[pos:np2]
                        pos = np2
                    else:
                        pos = _skip(buf, pos, np, tg & 7)
            vt = _new(Vote)
            _osa(vt, "__dict__", {
                "type": vt_y,
                "height": vt_e,
                "round": vt_r,
                "block_id": vt_b if vt_b is not None else NIL_BLOCK_ID,
                "timestamp_ns": vt_t,
                "validator_address": vt_a,
                "validator_index": vt_i,
                "signature": vt_g,
            })
            pos = np
            ap(vt)
            if len(votes) > mx:
                raise ValueError(f"vote batch exceeds {mx} votes")
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    vt_y = SignedMsgType.UNKNOWN
                    vt_e = vt_r = 0
                    vt_b = None
                    vt_t = 0
                    vt_a = b""
                    vt_i = -1
                    vt_g = b""
                    if pos < np and buf[pos] == 8:
                        pos += 1
                        v = buf[pos] if pos < np else 256
                        if v < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            v = (v & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            v = _r
                        vt_y = _SMT.get(v)
                        if vt_y is None:
                            vt_y = SignedMsgType(v)
                    if pos < np and buf[pos] == 17:
                        pos += 1
                        if pos + 8 > np:
                            raise ValueError("truncated sfixed64")
                        vt_e = _uq(buf, pos)[0]
                        pos += 8
                    if pos < np and buf[pos] == 25:
                        pos += 1
                        if pos + 8 > np:
                            raise ValueError("truncated sfixed64")
                        vt_r = _uq(buf, pos)[0]
                        pos += 8
                    if pos < np and buf[pos] == 34:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        _k = buf[pos:np2]
                        vt_b = _bm.get(_k)
                        if vt_b is None:
                            vt_b_h = b""
                            vt_b_p = None
                            if pos < np2 and buf[pos] == 10:
                                pos += 1
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                vt_b_h = buf[pos:np3]
                                pos = np3
                            if pos < np2 and buf[pos] == 18:
                                pos += 1
                                n3 = buf[pos] if pos < np2 else 256
                                if n3 < 128:
                                    pos += 1
                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np2:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n3 = _r
                                np3 = pos + n3
                                if np3 > np2:
                                    raise ValueError("truncated bytes")
                                vt_b_p_t = 0
                                vt_b_p_h = b""
                                if pos < np3 and buf[pos] == 8:
                                    pos += 1
                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                    if vt_b_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_b_p_t = _r
                                if pos < np3 and buf[pos] == 18:
                                    pos += 1
                                    n4 = buf[pos] if pos < np3 else 256
                                    if n4 < 128:
                                        pos += 1
                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np3:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n4 = _r
                                    np4 = pos + n4
                                    if np4 > np3:
                                        raise ValueError("truncated bytes")
                                    vt_b_p_h = buf[pos:np4]
                                    pos = np4
                                if pos < np3:
                                    while pos < np3:
                                        tg = buf[pos]
                                        if tg < 128:
                                            pos += 1
                                        else:
                                            tg, pos = _rv(buf, pos, np3)
                                        g4 = tg >> 3
                                        if g4 == 1:
                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                            if vt_b_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_b_p_t = _r
                                        elif g4 == 2:
                                            n4 = buf[pos] if pos < np3 else 256
                                            if n4 < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n4 = _r
                                            np4 = pos + n4
                                            if np4 > np3:
                                                raise ValueError("truncated bytes")
                                            vt_b_p_h = buf[pos:np4]
                                            pos = np4
                                        else:
                                            pos = _skip(buf, pos, np3, tg & 7)
                                if not vt_b_p_t and not vt_b_p_h:
                                    vt_b_p = _PSH0
                                else:
                                    vt_b_p = _new(PartSetHeader)
                                    _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                pos = np3
                            if pos < np2:
                                while pos < np2:
                                    tg = buf[pos]
                                    if tg < 128:
                                        pos += 1
                                    else:
                                        tg, pos = _rv(buf, pos, np2)
                                    g3 = tg >> 3
                                    if g3 == 1:
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        vt_b_h = buf[pos:np3]
                                        pos = np3
                                    elif g3 == 2:
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        vt_b_p_t = 0
                                        vt_b_p_h = b""
                                        if pos < np3 and buf[pos] == 8:
                                            pos += 1
                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                            if vt_b_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_b_p_t = _r
                                        if pos < np3 and buf[pos] == 18:
                                            pos += 1
                                            n4 = buf[pos] if pos < np3 else 256
                                            if n4 < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n4 = _r
                                            np4 = pos + n4
                                            if np4 > np3:
                                                raise ValueError("truncated bytes")
                                            vt_b_p_h = buf[pos:np4]
                                            pos = np4
                                        if pos < np3:
                                            while pos < np3:
                                                tg = buf[pos]
                                                if tg < 128:
                                                    pos += 1
                                                else:
                                                    tg, pos = _rv(buf, pos, np3)
                                                g4 = tg >> 3
                                                if g4 == 1:
                                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                                    if vt_b_p_t < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        vt_b_p_t = _r
                                                elif g4 == 2:
                                                    n4 = buf[pos] if pos < np3 else 256
                                                    if n4 < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        n4 = _r
                                                    np4 = pos + n4
                                                    if np4 > np3:
                                                        raise ValueError("truncated bytes")
                                                    vt_b_p_h = buf[pos:np4]
                                                    pos = np4
                                                else:
                                                    pos = _skip(buf, pos, np3, tg & 7)
                                        if not vt_b_p_t and not vt_b_p_h:
                                            vt_b_p = _PSH0
                                        else:
                                            vt_b_p = _new(PartSetHeader)
                                            _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                        pos = np3
                                    else:
                                        pos = _skip(buf, pos, np2, tg & 7)
                            if vt_b_p is None and not vt_b_h:
                                vt_b = NIL_BLOCK_ID
                            else:
                                vt_b = _new(BlockID)
                                _osa(vt_b, "__dict__", {
                                    "hash": vt_b_h,
                                    "part_set_header": vt_b_p if vt_b_p is not None else _PSH0,
                                })
                            _bm[_k] = vt_b
                        pos = np2
                    if pos < np and buf[pos] == 42:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        vt_t_s = vt_t_n = 0
                        if pos < np2 and buf[pos] == 8:
                            pos += 1
                            vt_t_s = buf[pos] if pos < np2 else 256
                            if vt_t_s < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_t_s = _r
                        if pos < np2 and buf[pos] == 16:
                            pos += 1
                            vt_t_n = buf[pos] if pos < np2 else 256
                            if vt_t_n < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                vt_t_n = _r
                        if pos < np2:
                            while pos < np2:
                                tg = buf[pos]
                                if tg < 128:
                                    pos += 1
                                else:
                                    tg, pos = _rv(buf, pos, np2)
                                g3 = tg >> 3
                                if g3 == 1:
                                    vt_t_s = buf[pos] if pos < np2 else 256
                                    if vt_t_s < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_t_s = _r
                                elif g3 == 2:
                                    vt_t_n = buf[pos] if pos < np2 else 256
                                    if vt_t_n < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_t_n = _r
                                else:
                                    pos = _skip(buf, pos, np2, tg & 7)
                        vt_t = vt_t_s * 1_000_000_000 + vt_t_n
                        pos = np2
                    if pos < np and buf[pos] == 50:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        vt_a = buf[pos:np2]
                        pos = np2
                    if pos < np and buf[pos] == 56:
                        pos += 1
                        v = buf[pos] if pos < np else 256
                        if v < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            v = (v & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            v = _r
                        vt_i = v - 1
                    if pos < np and buf[pos] == 66:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        vt_g = buf[pos:np2]
                        pos = np2
                    if pos < np:
                        while pos < np:
                            tg = buf[pos]
                            if tg < 128:
                                pos += 1
                            else:
                                tg, pos = _rv(buf, pos, np)
                            g2 = tg >> 3
                            if g2 == 1:
                                v = buf[pos] if pos < np else 256
                                if v < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    v = _r
                                vt_y = _SMT.get(v)
                                if vt_y is None:
                                    vt_y = SignedMsgType(v)
                            elif g2 == 2:
                                if pos + 8 > np:
                                    raise ValueError("truncated sfixed64")
                                vt_e = _uq(buf, pos)[0]
                                pos += 8
                            elif g2 == 3:
                                if pos + 8 > np:
                                    raise ValueError("truncated sfixed64")
                                vt_r = _uq(buf, pos)[0]
                                pos += 8
                            elif g2 == 4:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                _k = buf[pos:np2]
                                vt_b = _bm.get(_k)
                                if vt_b is None:
                                    vt_b_h = b""
                                    vt_b_p = None
                                    if pos < np2 and buf[pos] == 10:
                                        pos += 1
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        vt_b_h = buf[pos:np3]
                                        pos = np3
                                    if pos < np2 and buf[pos] == 18:
                                        pos += 1
                                        n3 = buf[pos] if pos < np2 else 256
                                        if n3 < 128:
                                            pos += 1
                                        elif pos + 1 < np2 and buf[pos + 1] < 128:
                                            n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                            pos += 2
                                        elif pos + 2 < np2 and buf[pos + 2] < 128:
                                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                            n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                            pos += 3
                                        else:
                                            _r = 0
                                            _s = 0
                                            while True:
                                                if pos >= np2:
                                                    raise ValueError("truncated varint")
                                                _b = buf[pos]
                                                pos += 1
                                                _r |= (_b & 0x7F) << _s
                                                if not _b & 0x80:
                                                    break
                                                _s += 7
                                                if _s > 70:
                                                    raise ValueError("varint too long")
                                            n3 = _r
                                        np3 = pos + n3
                                        if np3 > np2:
                                            raise ValueError("truncated bytes")
                                        vt_b_p_t = 0
                                        vt_b_p_h = b""
                                        if pos < np3 and buf[pos] == 8:
                                            pos += 1
                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                            if vt_b_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_b_p_t = _r
                                        if pos < np3 and buf[pos] == 18:
                                            pos += 1
                                            n4 = buf[pos] if pos < np3 else 256
                                            if n4 < 128:
                                                pos += 1
                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np3:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n4 = _r
                                            np4 = pos + n4
                                            if np4 > np3:
                                                raise ValueError("truncated bytes")
                                            vt_b_p_h = buf[pos:np4]
                                            pos = np4
                                        if pos < np3:
                                            while pos < np3:
                                                tg = buf[pos]
                                                if tg < 128:
                                                    pos += 1
                                                else:
                                                    tg, pos = _rv(buf, pos, np3)
                                                g4 = tg >> 3
                                                if g4 == 1:
                                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                                    if vt_b_p_t < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        vt_b_p_t = _r
                                                elif g4 == 2:
                                                    n4 = buf[pos] if pos < np3 else 256
                                                    if n4 < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        n4 = _r
                                                    np4 = pos + n4
                                                    if np4 > np3:
                                                        raise ValueError("truncated bytes")
                                                    vt_b_p_h = buf[pos:np4]
                                                    pos = np4
                                                else:
                                                    pos = _skip(buf, pos, np3, tg & 7)
                                        if not vt_b_p_t and not vt_b_p_h:
                                            vt_b_p = _PSH0
                                        else:
                                            vt_b_p = _new(PartSetHeader)
                                            _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                        pos = np3
                                    if pos < np2:
                                        while pos < np2:
                                            tg = buf[pos]
                                            if tg < 128:
                                                pos += 1
                                            else:
                                                tg, pos = _rv(buf, pos, np2)
                                            g3 = tg >> 3
                                            if g3 == 1:
                                                n3 = buf[pos] if pos < np2 else 256
                                                if n3 < 128:
                                                    pos += 1
                                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                                    pos += 2
                                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                    pos += 3
                                                else:
                                                    _r = 0
                                                    _s = 0
                                                    while True:
                                                        if pos >= np2:
                                                            raise ValueError("truncated varint")
                                                        _b = buf[pos]
                                                        pos += 1
                                                        _r |= (_b & 0x7F) << _s
                                                        if not _b & 0x80:
                                                            break
                                                        _s += 7
                                                        if _s > 70:
                                                            raise ValueError("varint too long")
                                                    n3 = _r
                                                np3 = pos + n3
                                                if np3 > np2:
                                                    raise ValueError("truncated bytes")
                                                vt_b_h = buf[pos:np3]
                                                pos = np3
                                            elif g3 == 2:
                                                n3 = buf[pos] if pos < np2 else 256
                                                if n3 < 128:
                                                    pos += 1
                                                elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                    n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                                    pos += 2
                                                elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                    n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                    pos += 3
                                                else:
                                                    _r = 0
                                                    _s = 0
                                                    while True:
                                                        if pos >= np2:
                                                            raise ValueError("truncated varint")
                                                        _b = buf[pos]
                                                        pos += 1
                                                        _r |= (_b & 0x7F) << _s
                                                        if not _b & 0x80:
                                                            break
                                                        _s += 7
                                                        if _s > 70:
                                                            raise ValueError("varint too long")
                                                    n3 = _r
                                                np3 = pos + n3
                                                if np3 > np2:
                                                    raise ValueError("truncated bytes")
                                                vt_b_p_t = 0
                                                vt_b_p_h = b""
                                                if pos < np3 and buf[pos] == 8:
                                                    pos += 1
                                                    vt_b_p_t = buf[pos] if pos < np3 else 256
                                                    if vt_b_p_t < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        vt_b_p_t = _r
                                                if pos < np3 and buf[pos] == 18:
                                                    pos += 1
                                                    n4 = buf[pos] if pos < np3 else 256
                                                    if n4 < 128:
                                                        pos += 1
                                                    elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                        n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                        pos += 2
                                                    elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                        n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                        pos += 3
                                                    else:
                                                        _r = 0
                                                        _s = 0
                                                        while True:
                                                            if pos >= np3:
                                                                raise ValueError("truncated varint")
                                                            _b = buf[pos]
                                                            pos += 1
                                                            _r |= (_b & 0x7F) << _s
                                                            if not _b & 0x80:
                                                                break
                                                            _s += 7
                                                            if _s > 70:
                                                                raise ValueError("varint too long")
                                                        n4 = _r
                                                    np4 = pos + n4
                                                    if np4 > np3:
                                                        raise ValueError("truncated bytes")
                                                    vt_b_p_h = buf[pos:np4]
                                                    pos = np4
                                                if pos < np3:
                                                    while pos < np3:
                                                        tg = buf[pos]
                                                        if tg < 128:
                                                            pos += 1
                                                        else:
                                                            tg, pos = _rv(buf, pos, np3)
                                                        g4 = tg >> 3
                                                        if g4 == 1:
                                                            vt_b_p_t = buf[pos] if pos < np3 else 256
                                                            if vt_b_p_t < 128:
                                                                pos += 1
                                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                                vt_b_p_t = (vt_b_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                                pos += 2
                                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                                vt_b_p_t = (vt_b_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                                pos += 3
                                                            else:
                                                                _r = 0
                                                                _s = 0
                                                                while True:
                                                                    if pos >= np3:
                                                                        raise ValueError("truncated varint")
                                                                    _b = buf[pos]
                                                                    pos += 1
                                                                    _r |= (_b & 0x7F) << _s
                                                                    if not _b & 0x80:
                                                                        break
                                                                    _s += 7
                                                                    if _s > 70:
                                                                        raise ValueError("varint too long")
                                                                vt_b_p_t = _r
                                                        elif g4 == 2:
                                                            n4 = buf[pos] if pos < np3 else 256
                                                            if n4 < 128:
                                                                pos += 1
                                                            elif pos + 1 < np3 and buf[pos + 1] < 128:
                                                                n4 = (n4 & 0x7F) | (buf[pos + 1] << 7)
                                                                pos += 2
                                                            elif pos + 2 < np3 and buf[pos + 2] < 128:
                                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                                n4 = (n4 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                                pos += 3
                                                            else:
                                                                _r = 0
                                                                _s = 0
                                                                while True:
                                                                    if pos >= np3:
                                                                        raise ValueError("truncated varint")
                                                                    _b = buf[pos]
                                                                    pos += 1
                                                                    _r |= (_b & 0x7F) << _s
                                                                    if not _b & 0x80:
                                                                        break
                                                                    _s += 7
                                                                    if _s > 70:
                                                                        raise ValueError("varint too long")
                                                                n4 = _r
                                                            np4 = pos + n4
                                                            if np4 > np3:
                                                                raise ValueError("truncated bytes")
                                                            vt_b_p_h = buf[pos:np4]
                                                            pos = np4
                                                        else:
                                                            pos = _skip(buf, pos, np3, tg & 7)
                                                if not vt_b_p_t and not vt_b_p_h:
                                                    vt_b_p = _PSH0
                                                else:
                                                    vt_b_p = _new(PartSetHeader)
                                                    _osa(vt_b_p, "__dict__", {"total": vt_b_p_t, "hash": vt_b_p_h})
                                                pos = np3
                                            else:
                                                pos = _skip(buf, pos, np2, tg & 7)
                                    if vt_b_p is None and not vt_b_h:
                                        vt_b = NIL_BLOCK_ID
                                    else:
                                        vt_b = _new(BlockID)
                                        _osa(vt_b, "__dict__", {
                                            "hash": vt_b_h,
                                            "part_set_header": vt_b_p if vt_b_p is not None else _PSH0,
                                        })
                                    _bm[_k] = vt_b
                                pos = np2
                            elif g2 == 5:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                vt_t_s = vt_t_n = 0
                                if pos < np2 and buf[pos] == 8:
                                    pos += 1
                                    vt_t_s = buf[pos] if pos < np2 else 256
                                    if vt_t_s < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_t_s = _r
                                if pos < np2 and buf[pos] == 16:
                                    pos += 1
                                    vt_t_n = buf[pos] if pos < np2 else 256
                                    if vt_t_n < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        vt_t_n = _r
                                if pos < np2:
                                    while pos < np2:
                                        tg = buf[pos]
                                        if tg < 128:
                                            pos += 1
                                        else:
                                            tg, pos = _rv(buf, pos, np2)
                                        g3 = tg >> 3
                                        if g3 == 1:
                                            vt_t_s = buf[pos] if pos < np2 else 256
                                            if vt_t_s < 128:
                                                pos += 1
                                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                vt_t_s = (vt_t_s & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_t_s = (vt_t_s & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np2:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_t_s = _r
                                        elif g3 == 2:
                                            vt_t_n = buf[pos] if pos < np2 else 256
                                            if vt_t_n < 128:
                                                pos += 1
                                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                vt_t_n = (vt_t_n & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                vt_t_n = (vt_t_n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np2:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                vt_t_n = _r
                                        else:
                                            pos = _skip(buf, pos, np2, tg & 7)
                                vt_t = vt_t_s * 1_000_000_000 + vt_t_n
                                pos = np2
                            elif g2 == 6:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                vt_a = buf[pos:np2]
                                pos = np2
                            elif g2 == 7:
                                v = buf[pos] if pos < np else 256
                                if v < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    v = _r
                                vt_i = v - 1
                            elif g2 == 8:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                vt_g = buf[pos:np2]
                                pos = np2
                            else:
                                pos = _skip(buf, pos, np, tg & 7)
                    vt = _new(Vote)
                    _osa(vt, "__dict__", {
                        "type": vt_y,
                        "height": vt_e,
                        "round": vt_r,
                        "block_id": vt_b if vt_b is not None else NIL_BLOCK_ID,
                        "timestamp_ns": vt_t,
                        "validator_address": vt_a,
                        "validator_index": vt_i,
                        "signature": vt_g,
                    })
                    pos = np
                    ap(vt)
                    if len(votes) > mx:
                        raise ValueError(f"vote batch exceeds {mx} votes")
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        m = _new(VoteBatchMessage)
        _osa(m, "__dict__", {"votes": tuple(votes)})
        return m
    if f == 5:
        height = round_ = 0
        part = None
        if pos < bend and buf[pos] == 8:
            pos += 1
            height = buf[pos] if pos < bend else 256
            if height < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                height = (height & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                height = _r
        if pos < bend and buf[pos] == 16:
            pos += 1
            round_ = buf[pos] if pos < bend else 256
            if round_ < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                round_ = _r
        if pos < bend and buf[pos] == 26:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            part_i = 0
            part_d = b""
            part_p = None
            if pos < np and buf[pos] == 8:
                pos += 1
                v = buf[pos] if pos < np else 256
                if v < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    v = _r
                part_i = v - 1
            if pos < np and buf[pos] == 18:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                part_d = buf[pos:np2]
                pos = np2
            if pos < np and buf[pos] == 26:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                part_p_t = part_p_i = 0
                part_p_l = b""
                part_p_a = []
                if pos < np2 and buf[pos] == 8:
                    pos += 1
                    part_p_t = buf[pos] if pos < np2 else 256
                    if part_p_t < 128:
                        pos += 1
                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                        part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np2:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        part_p_t = _r
                if pos < np2 and buf[pos] == 16:
                    pos += 1
                    part_p_i = buf[pos] if pos < np2 else 256
                    if part_p_i < 128:
                        pos += 1
                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                        part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np2:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        part_p_i = _r
                if pos < np2 and buf[pos] == 26:
                    pos += 1
                    n3 = buf[pos] if pos < np2 else 256
                    if n3 < 128:
                        pos += 1
                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np2:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n3 = _r
                    np3 = pos + n3
                    if np3 > np2:
                        raise ValueError("truncated bytes")
                    part_p_l = buf[pos:np3]
                    pos = np3
                if pos < np2 and buf[pos] == 34:
                    _pap = part_p_a.append
                    _pmx = _mkl.MAX_PROOF_AUNTS
                while pos < np2 and buf[pos] == 34:
                    pos += 1
                    n3 = buf[pos] if pos < np2 else 256
                    if n3 < 128:
                        pos += 1
                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= np2:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n3 = _r
                    np3 = pos + n3
                    if np3 > np2:
                        raise ValueError("truncated bytes")
                    _pap(buf[pos:np3])
                    pos = np3
                    if len(part_p_a) > _pmx:
                        raise ValueError(f"merkle proof aunts exceed {_pmx}")
                if pos < np2:
                    while pos < np2:
                        tg = buf[pos]
                        if tg < 128:
                            pos += 1
                        else:
                            tg, pos = _rv(buf, pos, np2)
                        g3 = tg >> 3
                        if g3 == 1:
                            part_p_t = buf[pos] if pos < np2 else 256
                            if part_p_t < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                part_p_t = _r
                        elif g3 == 2:
                            part_p_i = buf[pos] if pos < np2 else 256
                            if part_p_i < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                part_p_i = _r
                        elif g3 == 3:
                            n3 = buf[pos] if pos < np2 else 256
                            if n3 < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n3 = _r
                            np3 = pos + n3
                            if np3 > np2:
                                raise ValueError("truncated bytes")
                            part_p_l = buf[pos:np3]
                            pos = np3
                        elif g3 == 4:
                            n3 = buf[pos] if pos < np2 else 256
                            if n3 < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n3 = _r
                            np3 = pos + n3
                            if np3 > np2:
                                raise ValueError("truncated bytes")
                            part_p_a.append(buf[pos:np3])
                            pos = np3
                            if len(part_p_a) > _mkl.MAX_PROOF_AUNTS:
                                raise ValueError(
                                    f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                                )
                        else:
                            pos = _skip(buf, pos, np2, tg & 7)
                part_p = _new(_Proof)
                _osa(part_p, "__dict__", {
                    "total": part_p_t,
                    "index": part_p_i,
                    "leaf_hash": part_p_l,
                    "aunts": part_p_a,
                })
                pos = np2
            if pos < np:
                while pos < np:
                    tg = buf[pos]
                    if tg < 128:
                        pos += 1
                    else:
                        tg, pos = _rv(buf, pos, np)
                    g2 = tg >> 3
                    if g2 == 1:
                        v = buf[pos] if pos < np else 256
                        if v < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            v = (v & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            v = _r
                        part_i = v - 1
                    elif g2 == 2:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        part_d = buf[pos:np2]
                        pos = np2
                    elif g2 == 3:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        part_p_t = part_p_i = 0
                        part_p_l = b""
                        part_p_a = []
                        if pos < np2 and buf[pos] == 8:
                            pos += 1
                            part_p_t = buf[pos] if pos < np2 else 256
                            if part_p_t < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                part_p_t = _r
                        if pos < np2 and buf[pos] == 16:
                            pos += 1
                            part_p_i = buf[pos] if pos < np2 else 256
                            if part_p_i < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                part_p_i = _r
                        if pos < np2 and buf[pos] == 26:
                            pos += 1
                            n3 = buf[pos] if pos < np2 else 256
                            if n3 < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n3 = _r
                            np3 = pos + n3
                            if np3 > np2:
                                raise ValueError("truncated bytes")
                            part_p_l = buf[pos:np3]
                            pos = np3
                        if pos < np2 and buf[pos] == 34:
                            _pap = part_p_a.append
                            _pmx = _mkl.MAX_PROOF_AUNTS
                        while pos < np2 and buf[pos] == 34:
                            pos += 1
                            n3 = buf[pos] if pos < np2 else 256
                            if n3 < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n3 = _r
                            np3 = pos + n3
                            if np3 > np2:
                                raise ValueError("truncated bytes")
                            _pap(buf[pos:np3])
                            pos = np3
                            if len(part_p_a) > _pmx:
                                raise ValueError(f"merkle proof aunts exceed {_pmx}")
                        if pos < np2:
                            while pos < np2:
                                tg = buf[pos]
                                if tg < 128:
                                    pos += 1
                                else:
                                    tg, pos = _rv(buf, pos, np2)
                                g3 = tg >> 3
                                if g3 == 1:
                                    part_p_t = buf[pos] if pos < np2 else 256
                                    if part_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        part_p_t = _r
                                elif g3 == 2:
                                    part_p_i = buf[pos] if pos < np2 else 256
                                    if part_p_i < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        part_p_i = _r
                                elif g3 == 3:
                                    n3 = buf[pos] if pos < np2 else 256
                                    if n3 < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n3 = _r
                                    np3 = pos + n3
                                    if np3 > np2:
                                        raise ValueError("truncated bytes")
                                    part_p_l = buf[pos:np3]
                                    pos = np3
                                elif g3 == 4:
                                    n3 = buf[pos] if pos < np2 else 256
                                    if n3 < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n3 = _r
                                    np3 = pos + n3
                                    if np3 > np2:
                                        raise ValueError("truncated bytes")
                                    part_p_a.append(buf[pos:np3])
                                    pos = np3
                                    if len(part_p_a) > _mkl.MAX_PROOF_AUNTS:
                                        raise ValueError(
                                            f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                                        )
                                else:
                                    pos = _skip(buf, pos, np2, tg & 7)
                        part_p = _new(_Proof)
                        _osa(part_p, "__dict__", {
                            "total": part_p_t,
                            "index": part_p_i,
                            "leaf_hash": part_p_l,
                            "aunts": part_p_a,
                        })
                        pos = np2
                    else:
                        pos = _skip(buf, pos, np, tg & 7)
            if part_p is None:
                part_p = _new(_Proof)
                _osa(part_p, "__dict__", {"total": 0, "index": 0, "leaf_hash": b"", "aunts": []})
            part = _new(Part)
            _osa(part, "__dict__", {"index": part_i, "bytes_": part_d, "proof": part_p})
            pos = np
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    height = buf[pos] if pos < bend else 256
                    if height < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        height = (height & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        height = _r
                elif g == 2:
                    round_ = buf[pos] if pos < bend else 256
                    if round_ < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        round_ = _r
                elif g == 3:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    part_i = 0
                    part_d = b""
                    part_p = None
                    if pos < np and buf[pos] == 8:
                        pos += 1
                        v = buf[pos] if pos < np else 256
                        if v < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            v = (v & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            v = _r
                        part_i = v - 1
                    if pos < np and buf[pos] == 18:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        part_d = buf[pos:np2]
                        pos = np2
                    if pos < np and buf[pos] == 26:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        part_p_t = part_p_i = 0
                        part_p_l = b""
                        part_p_a = []
                        if pos < np2 and buf[pos] == 8:
                            pos += 1
                            part_p_t = buf[pos] if pos < np2 else 256
                            if part_p_t < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                part_p_t = _r
                        if pos < np2 and buf[pos] == 16:
                            pos += 1
                            part_p_i = buf[pos] if pos < np2 else 256
                            if part_p_i < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                part_p_i = _r
                        if pos < np2 and buf[pos] == 26:
                            pos += 1
                            n3 = buf[pos] if pos < np2 else 256
                            if n3 < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n3 = _r
                            np3 = pos + n3
                            if np3 > np2:
                                raise ValueError("truncated bytes")
                            part_p_l = buf[pos:np3]
                            pos = np3
                        if pos < np2 and buf[pos] == 34:
                            _pap = part_p_a.append
                            _pmx = _mkl.MAX_PROOF_AUNTS
                        while pos < np2 and buf[pos] == 34:
                            pos += 1
                            n3 = buf[pos] if pos < np2 else 256
                            if n3 < 128:
                                pos += 1
                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                pos += 2
                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                pos += 3
                            else:
                                _r = 0
                                _s = 0
                                while True:
                                    if pos >= np2:
                                        raise ValueError("truncated varint")
                                    _b = buf[pos]
                                    pos += 1
                                    _r |= (_b & 0x7F) << _s
                                    if not _b & 0x80:
                                        break
                                    _s += 7
                                    if _s > 70:
                                        raise ValueError("varint too long")
                                n3 = _r
                            np3 = pos + n3
                            if np3 > np2:
                                raise ValueError("truncated bytes")
                            _pap(buf[pos:np3])
                            pos = np3
                            if len(part_p_a) > _pmx:
                                raise ValueError(f"merkle proof aunts exceed {_pmx}")
                        if pos < np2:
                            while pos < np2:
                                tg = buf[pos]
                                if tg < 128:
                                    pos += 1
                                else:
                                    tg, pos = _rv(buf, pos, np2)
                                g3 = tg >> 3
                                if g3 == 1:
                                    part_p_t = buf[pos] if pos < np2 else 256
                                    if part_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        part_p_t = _r
                                elif g3 == 2:
                                    part_p_i = buf[pos] if pos < np2 else 256
                                    if part_p_i < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        part_p_i = _r
                                elif g3 == 3:
                                    n3 = buf[pos] if pos < np2 else 256
                                    if n3 < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n3 = _r
                                    np3 = pos + n3
                                    if np3 > np2:
                                        raise ValueError("truncated bytes")
                                    part_p_l = buf[pos:np3]
                                    pos = np3
                                elif g3 == 4:
                                    n3 = buf[pos] if pos < np2 else 256
                                    if n3 < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n3 = _r
                                    np3 = pos + n3
                                    if np3 > np2:
                                        raise ValueError("truncated bytes")
                                    part_p_a.append(buf[pos:np3])
                                    pos = np3
                                    if len(part_p_a) > _mkl.MAX_PROOF_AUNTS:
                                        raise ValueError(
                                            f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                                        )
                                else:
                                    pos = _skip(buf, pos, np2, tg & 7)
                        part_p = _new(_Proof)
                        _osa(part_p, "__dict__", {
                            "total": part_p_t,
                            "index": part_p_i,
                            "leaf_hash": part_p_l,
                            "aunts": part_p_a,
                        })
                        pos = np2
                    if pos < np:
                        while pos < np:
                            tg = buf[pos]
                            if tg < 128:
                                pos += 1
                            else:
                                tg, pos = _rv(buf, pos, np)
                            g2 = tg >> 3
                            if g2 == 1:
                                v = buf[pos] if pos < np else 256
                                if v < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    v = (v & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    v = _r
                                part_i = v - 1
                            elif g2 == 2:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                part_d = buf[pos:np2]
                                pos = np2
                            elif g2 == 3:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                part_p_t = part_p_i = 0
                                part_p_l = b""
                                part_p_a = []
                                if pos < np2 and buf[pos] == 8:
                                    pos += 1
                                    part_p_t = buf[pos] if pos < np2 else 256
                                    if part_p_t < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        part_p_t = _r
                                if pos < np2 and buf[pos] == 16:
                                    pos += 1
                                    part_p_i = buf[pos] if pos < np2 else 256
                                    if part_p_i < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        part_p_i = _r
                                if pos < np2 and buf[pos] == 26:
                                    pos += 1
                                    n3 = buf[pos] if pos < np2 else 256
                                    if n3 < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n3 = _r
                                    np3 = pos + n3
                                    if np3 > np2:
                                        raise ValueError("truncated bytes")
                                    part_p_l = buf[pos:np3]
                                    pos = np3
                                if pos < np2 and buf[pos] == 34:
                                    _pap = part_p_a.append
                                    _pmx = _mkl.MAX_PROOF_AUNTS
                                while pos < np2 and buf[pos] == 34:
                                    pos += 1
                                    n3 = buf[pos] if pos < np2 else 256
                                    if n3 < 128:
                                        pos += 1
                                    elif pos + 1 < np2 and buf[pos + 1] < 128:
                                        n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                        pos += 2
                                    elif pos + 2 < np2 and buf[pos + 2] < 128:
                                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                        n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                        pos += 3
                                    else:
                                        _r = 0
                                        _s = 0
                                        while True:
                                            if pos >= np2:
                                                raise ValueError("truncated varint")
                                            _b = buf[pos]
                                            pos += 1
                                            _r |= (_b & 0x7F) << _s
                                            if not _b & 0x80:
                                                break
                                            _s += 7
                                            if _s > 70:
                                                raise ValueError("varint too long")
                                        n3 = _r
                                    np3 = pos + n3
                                    if np3 > np2:
                                        raise ValueError("truncated bytes")
                                    _pap(buf[pos:np3])
                                    pos = np3
                                    if len(part_p_a) > _pmx:
                                        raise ValueError(f"merkle proof aunts exceed {_pmx}")
                                if pos < np2:
                                    while pos < np2:
                                        tg = buf[pos]
                                        if tg < 128:
                                            pos += 1
                                        else:
                                            tg, pos = _rv(buf, pos, np2)
                                        g3 = tg >> 3
                                        if g3 == 1:
                                            part_p_t = buf[pos] if pos < np2 else 256
                                            if part_p_t < 128:
                                                pos += 1
                                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                part_p_t = (part_p_t & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                part_p_t = (part_p_t & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np2:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                part_p_t = _r
                                        elif g3 == 2:
                                            part_p_i = buf[pos] if pos < np2 else 256
                                            if part_p_i < 128:
                                                pos += 1
                                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                part_p_i = (part_p_i & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                part_p_i = (part_p_i & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np2:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                part_p_i = _r
                                        elif g3 == 3:
                                            n3 = buf[pos] if pos < np2 else 256
                                            if n3 < 128:
                                                pos += 1
                                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np2:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n3 = _r
                                            np3 = pos + n3
                                            if np3 > np2:
                                                raise ValueError("truncated bytes")
                                            part_p_l = buf[pos:np3]
                                            pos = np3
                                        elif g3 == 4:
                                            n3 = buf[pos] if pos < np2 else 256
                                            if n3 < 128:
                                                pos += 1
                                            elif pos + 1 < np2 and buf[pos + 1] < 128:
                                                n3 = (n3 & 0x7F) | (buf[pos + 1] << 7)
                                                pos += 2
                                            elif pos + 2 < np2 and buf[pos + 2] < 128:
                                                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                                n3 = (n3 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                                pos += 3
                                            else:
                                                _r = 0
                                                _s = 0
                                                while True:
                                                    if pos >= np2:
                                                        raise ValueError("truncated varint")
                                                    _b = buf[pos]
                                                    pos += 1
                                                    _r |= (_b & 0x7F) << _s
                                                    if not _b & 0x80:
                                                        break
                                                    _s += 7
                                                    if _s > 70:
                                                        raise ValueError("varint too long")
                                                n3 = _r
                                            np3 = pos + n3
                                            if np3 > np2:
                                                raise ValueError("truncated bytes")
                                            part_p_a.append(buf[pos:np3])
                                            pos = np3
                                            if len(part_p_a) > _mkl.MAX_PROOF_AUNTS:
                                                raise ValueError(
                                                    f"merkle proof aunts exceed {_mkl.MAX_PROOF_AUNTS}"
                                                )
                                        else:
                                            pos = _skip(buf, pos, np2, tg & 7)
                                part_p = _new(_Proof)
                                _osa(part_p, "__dict__", {
                                    "total": part_p_t,
                                    "index": part_p_i,
                                    "leaf_hash": part_p_l,
                                    "aunts": part_p_a,
                                })
                                pos = np2
                            else:
                                pos = _skip(buf, pos, np, tg & 7)
                    if part_p is None:
                        part_p = _new(_Proof)
                        _osa(part_p, "__dict__", {"total": 0, "index": 0, "leaf_hash": b"", "aunts": []})
                    part = _new(Part)
                    _osa(part, "__dict__", {"index": part_i, "bytes_": part_d, "proof": part_p})
                    pos = np
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        m = _new(BlockPartMessage)
        _osa(m, "__dict__", {"height": height, "round": round_, "part": part})
        return m
    if f == 6:
        m = _new(VoteMessage)
        _osa(m, "__dict__", {"vote": decode_vote(buf, pos, bend)})
        return m
    if f == 11:
        entries = []
        ap = entries.append
        mx = _msgs.MAX_BATCH_VOTES
        while pos < bend and buf[pos] == 10:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            ap(_d_has_vote(buf, pos, np))
            pos = np
            if len(entries) > mx:
                raise ValueError(f"has-vote batch exceeds {mx} entries")
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    ap(_d_has_vote(buf, pos, np))
                    pos = np
                    if len(entries) > mx:
                        raise ValueError(f"has-vote batch exceeds {mx} entries")
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        m = _new(HasVoteBatchMessage)
        _osa(m, "__dict__", {"entries": tuple(entries)})
        return m
    if f == 7:
        return _d_has_vote(buf, pos, bend)
    if f == 1:
        height = step = ss = 0
        round_ = lc = -1
        if pos < bend and buf[pos] == 8:
            pos += 1
            height = buf[pos] if pos < bend else 256
            if height < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                height = (height & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                height = _r
        if pos < bend and buf[pos] == 16:
            pos += 1
            v = buf[pos] if pos < bend else 256
            if v < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                v = (v & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                v = _r
            round_ = v - 1
        if pos < bend and buf[pos] == 24:
            pos += 1
            step = buf[pos] if pos < bend else 256
            if step < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                step = (step & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                step = (step & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                step = _r
        if pos < bend and buf[pos] == 32:
            pos += 1
            ss = buf[pos] if pos < bend else 256
            if ss < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                ss = (ss & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                ss = (ss & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                ss = _r
        if pos < bend and buf[pos] == 40:
            pos += 1
            v = buf[pos] if pos < bend else 256
            if v < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                v = (v & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                v = _r
            lc = v - 1
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    height = buf[pos] if pos < bend else 256
                    if height < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        height = (height & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        height = _r
                elif g == 2:
                    v = buf[pos] if pos < bend else 256
                    if v < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        v = (v & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        v = _r
                    round_ = v - 1
                elif g == 3:
                    step = buf[pos] if pos < bend else 256
                    if step < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        step = (step & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        step = (step & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        step = _r
                elif g == 4:
                    ss = buf[pos] if pos < bend else 256
                    if ss < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        ss = (ss & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        ss = (ss & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        ss = _r
                elif g == 5:
                    v = buf[pos] if pos < bend else 256
                    if v < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        v = (v & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        v = _r
                    lc = v - 1
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        m = _new(NewRoundStepMessage)
        _osa(m, "__dict__", {
            "height": height,
            "round": round_,
            "step": step,
            "seconds_since_start_time": ss,
            "last_commit_round": lc,
        })
        return m
    if f == 2:
        height = round_ = total = 0
        ph = b""
        bits = None
        is_commit = False
        if pos < bend and buf[pos] == 8:
            pos += 1
            height = buf[pos] if pos < bend else 256
            if height < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                height = (height & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                height = _r
        if pos < bend and buf[pos] == 16:
            pos += 1
            round_ = buf[pos] if pos < bend else 256
            if round_ < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                round_ = _r
        if pos < bend and buf[pos] == 26:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            if pos < np and buf[pos] == 8:
                pos += 1
                total = buf[pos] if pos < np else 256
                if total < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    total = (total & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    total = (total & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    total = _r
            if pos < np and buf[pos] == 18:
                pos += 1
                n2 = buf[pos] if pos < np else 256
                if n2 < 128:
                    pos += 1
                elif pos + 1 < np and buf[pos + 1] < 128:
                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                    pos += 2
                elif pos + 2 < np and buf[pos + 2] < 128:
                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                    pos += 3
                else:
                    _r = 0
                    _s = 0
                    while True:
                        if pos >= np:
                            raise ValueError("truncated varint")
                        _b = buf[pos]
                        pos += 1
                        _r |= (_b & 0x7F) << _s
                        if not _b & 0x80:
                            break
                        _s += 7
                        if _s > 70:
                            raise ValueError("varint too long")
                    n2 = _r
                np2 = pos + n2
                if np2 > np:
                    raise ValueError("truncated bytes")
                ph = buf[pos:np2]
                pos = np2
            if pos < np:
                while pos < np:
                    tg = buf[pos]
                    if tg < 128:
                        pos += 1
                    else:
                        tg, pos = _rv(buf, pos, np)
                    g2 = tg >> 3
                    if g2 == 1:
                        total = buf[pos] if pos < np else 256
                        if total < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            total = (total & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            total = (total & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            total = _r
                    elif g2 == 2:
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        ph = buf[pos:np2]
                        pos = np2
                    else:
                        pos = _skip(buf, pos, np, tg & 7)
            pos = np
        if pos < bend and buf[pos] == 34:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            bits = _d_bits(buf, pos, np)
            pos = np
        if pos < bend and buf[pos] == 40:
            pos += 1
            v = buf[pos] if pos < bend else 256
            if v < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                v = (v & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                v = _r
            is_commit = v == 1
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    height = buf[pos] if pos < bend else 256
                    if height < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        height = (height & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        height = _r
                elif g == 2:
                    round_ = buf[pos] if pos < bend else 256
                    if round_ < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        round_ = _r
                elif g == 3:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    if pos < np and buf[pos] == 8:
                        pos += 1
                        total = buf[pos] if pos < np else 256
                        if total < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            total = (total & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            total = (total & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            total = _r
                    if pos < np and buf[pos] == 18:
                        pos += 1
                        n2 = buf[pos] if pos < np else 256
                        if n2 < 128:
                            pos += 1
                        elif pos + 1 < np and buf[pos + 1] < 128:
                            n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                            pos += 2
                        elif pos + 2 < np and buf[pos + 2] < 128:
                            # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                            n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                            pos += 3
                        else:
                            _r = 0
                            _s = 0
                            while True:
                                if pos >= np:
                                    raise ValueError("truncated varint")
                                _b = buf[pos]
                                pos += 1
                                _r |= (_b & 0x7F) << _s
                                if not _b & 0x80:
                                    break
                                _s += 7
                                if _s > 70:
                                    raise ValueError("varint too long")
                            n2 = _r
                        np2 = pos + n2
                        if np2 > np:
                            raise ValueError("truncated bytes")
                        ph = buf[pos:np2]
                        pos = np2
                    if pos < np:
                        while pos < np:
                            tg = buf[pos]
                            if tg < 128:
                                pos += 1
                            else:
                                tg, pos = _rv(buf, pos, np)
                            g2 = tg >> 3
                            if g2 == 1:
                                total = buf[pos] if pos < np else 256
                                if total < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    total = (total & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    total = (total & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    total = _r
                            elif g2 == 2:
                                n2 = buf[pos] if pos < np else 256
                                if n2 < 128:
                                    pos += 1
                                elif pos + 1 < np and buf[pos + 1] < 128:
                                    n2 = (n2 & 0x7F) | (buf[pos + 1] << 7)
                                    pos += 2
                                elif pos + 2 < np and buf[pos + 2] < 128:
                                    # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                                    n2 = (n2 & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                                    pos += 3
                                else:
                                    _r = 0
                                    _s = 0
                                    while True:
                                        if pos >= np:
                                            raise ValueError("truncated varint")
                                        _b = buf[pos]
                                        pos += 1
                                        _r |= (_b & 0x7F) << _s
                                        if not _b & 0x80:
                                            break
                                        _s += 7
                                        if _s > 70:
                                            raise ValueError("varint too long")
                                    n2 = _r
                                np2 = pos + n2
                                if np2 > np:
                                    raise ValueError("truncated bytes")
                                ph = buf[pos:np2]
                                pos = np2
                            else:
                                pos = _skip(buf, pos, np, tg & 7)
                    pos = np
                elif g == 4:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    bits = _d_bits(buf, pos, np)
                    pos = np
                elif g == 5:
                    v = buf[pos] if pos < bend else 256
                    if v < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        v = (v & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        v = _r
                    is_commit = v == 1
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        m = _new(NewValidBlockMessage)
        _osa(m, "__dict__", {
            "height": height,
            "round": round_,
            "block_part_set_header": (total, ph),
            "block_parts": bits if bits is not None else BitArray(0),
            "is_commit": is_commit,
        })
        return m
    if f == 3:
        m = _new(ProposalMessage)
        _osa(m, "__dict__", {"proposal": decode_proposal(buf, pos, bend)})
        return m
    if f == 4:
        height = pol = 0
        bits = None
        if pos < bend and buf[pos] == 8:
            pos += 1
            height = buf[pos] if pos < bend else 256
            if height < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                height = (height & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                height = _r
        if pos < bend and buf[pos] == 16:
            pos += 1
            pol = buf[pos] if pos < bend else 256
            if pol < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                pol = (pol & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                pol = (pol & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                pol = _r
        if pos < bend and buf[pos] == 26:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            bits = _d_bits(buf, pos, np)
            pos = np
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    height = buf[pos] if pos < bend else 256
                    if height < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        height = (height & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        height = _r
                elif g == 2:
                    pol = buf[pos] if pos < bend else 256
                    if pol < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        pol = (pol & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        pol = (pol & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        pol = _r
                elif g == 3:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    bits = _d_bits(buf, pos, np)
                    pos = np
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        m = _new(ProposalPOLMessage)
        _osa(m, "__dict__", {
            "height": height,
            "proposal_pol_round": pol,
            "proposal_pol": bits if bits is not None else BitArray(0),
        })
        return m
    if f == 8 or f == 9:
        height = round_ = 0
        type_ = SignedMsgType.UNKNOWN
        bid = None
        bits = None
        if pos < bend and buf[pos] == 8:
            pos += 1
            height = buf[pos] if pos < bend else 256
            if height < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                height = (height & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                height = _r
        if pos < bend and buf[pos] == 16:
            pos += 1
            round_ = buf[pos] if pos < bend else 256
            if round_ < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                round_ = _r
        if pos < bend and buf[pos] == 24:
            pos += 1
            v = buf[pos] if pos < bend else 256
            if v < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                v = (v & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                v = _r
            type_ = _SMT.get(v)
            if type_ is None:
                type_ = SignedMsgType(v)
        if pos < bend and buf[pos] == 34:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            bid = decode_block_id(buf, pos, np)
            pos = np
        if pos < bend and buf[pos] == 42:
            pos += 1
            n = buf[pos] if pos < bend else 256
            if n < 128:
                pos += 1
            elif pos + 1 < bend and buf[pos + 1] < 128:
                n = (n & 0x7F) | (buf[pos + 1] << 7)
                pos += 2
            elif pos + 2 < bend and buf[pos + 2] < 128:
                # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                pos += 3
            else:
                _r = 0
                _s = 0
                while True:
                    if pos >= bend:
                        raise ValueError("truncated varint")
                    _b = buf[pos]
                    pos += 1
                    _r |= (_b & 0x7F) << _s
                    if not _b & 0x80:
                        break
                    _s += 7
                    if _s > 70:
                        raise ValueError("varint too long")
                n = _r
            np = pos + n
            if np > bend:
                raise ValueError("truncated bytes")
            bits = _d_bits(buf, pos, np)
            pos = np
        if pos < bend:
            while pos < bend:
                tg = buf[pos]
                if tg < 128:
                    pos += 1
                else:
                    tg, pos = _rv(buf, pos, bend)
                g = tg >> 3
                if g == 1:
                    height = buf[pos] if pos < bend else 256
                    if height < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        height = (height & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        height = (height & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        height = _r
                elif g == 2:
                    round_ = buf[pos] if pos < bend else 256
                    if round_ < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        round_ = (round_ & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        round_ = (round_ & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        round_ = _r
                elif g == 3:
                    v = buf[pos] if pos < bend else 256
                    if v < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        v = (v & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        v = (v & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        v = _r
                    type_ = _SMT.get(v)
                    if type_ is None:
                        type_ = SignedMsgType(v)
                elif g == 4:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    bid = decode_block_id(buf, pos, np)
                    pos = np
                elif g == 5:
                    n = buf[pos] if pos < bend else 256
                    if n < 128:
                        pos += 1
                    elif pos + 1 < bend and buf[pos + 1] < 128:
                        n = (n & 0x7F) | (buf[pos + 1] << 7)
                        pos += 2
                    elif pos + 2 < bend and buf[pos + 2] < 128:
                        # branch 2 failing with pos+2 in range means buf[pos+1] >= 128
                        n = (n & 0x7F) | ((buf[pos + 1] & 0x7F) << 7) | (buf[pos + 2] << 14)
                        pos += 3
                    else:
                        _r = 0
                        _s = 0
                        while True:
                            if pos >= bend:
                                raise ValueError("truncated varint")
                            _b = buf[pos]
                            pos += 1
                            _r |= (_b & 0x7F) << _s
                            if not _b & 0x80:
                                break
                            _s += 7
                            if _s > 70:
                                raise ValueError("varint too long")
                        n = _r
                    np = pos + n
                    if np > bend:
                        raise ValueError("truncated bytes")
                    bits = _d_bits(buf, pos, np)
                    pos = np
                else:
                    pos = _skip(buf, pos, bend, tg & 7)
        if f == 8:
            m = _new(VoteSetMaj23Message)
            _osa(m, "__dict__", {
                "height": height,
                "round": round_,
                "type": type_,
                "block_id": bid if bid is not None else NIL_BLOCK_ID,
            })
            return m
        m = _new(VoteSetBitsMessage)
        _osa(m, "__dict__", {
            "height": height,
            "round": round_,
            "type": type_,
            "block_id": bid if bid is not None else NIL_BLOCK_ID,
            "votes": bits if bits is not None else BitArray(0),
        })
        return m
    raise ValueError(f"unknown consensus message tag {f}")


_ENC = {
    NewRoundStepMessage: _e_nrs,
    NewValidBlockMessage: _e_nvb,
    ProposalMessage: _e_prop,
    ProposalPOLMessage: _e_pol,
    BlockPartMessage: _e_bpart,
    VoteMessage: _e_vote,
    VoteBatchMessage: _e_vb,
    HasVoteMessage: _e_hv,
    HasVoteBatchMessage: _e_hvb,
    VoteSetMaj23Message: _e_vsm,
    VoteSetBitsMessage: _e_vsb,
}


def encode_message(msg):
    e = _ENC.get(msg.__class__)
    if e is None:
        # subclasses and foreign types take the interpreted isinstance
        # chain (identical TypeError for unknown message types)
        return _msgs.encode_message_py(msg)
    return e(msg)


_msgs._adopt_generated(encode_message, decode_message)
