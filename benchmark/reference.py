"""The plain reference: what a commit, a quorum and a kvstore state hash
ARE, written straight from the Tendermint specification, with OpenSSL's
ed25519 verify underneath.

It imports nothing of tendermint_tpu and takes nothing the program made:
its inputs are plain data (bytes, ints, tuples) that the drivers read off
the seeded fixture, and its own canonical-vote encoder builds the signed
bytes again from those fields. For honestly signed and bit-flipped
signatures strict RFC 8032 and ZIP-215 agree, so OpenSSL's verdict is the
configuration's verdict on every signature the benchmark makes.

    CommitData   what one commit says, as plain fields
    commit_verdict()   light semantics: the for-block signatures in index
                       order, each one verified, until MORE than 2/3 of
                       the total power has signed exactly this block ID
    kv_state_hash()    RFC 6962 merkle root over the sorted (key, value)
                       pairs a kvstore holds after a list of transactions
"""

from __future__ import annotations

import hashlib
import os
import pickle
import struct
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from fractions import Fraction

from cryptography.exceptions import InvalidSignature
from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PublicKey

PRECOMMIT = 2
FLAG_COMMIT = 2
NANOS = 1_000_000_000


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _tag(field: int, wire: int) -> bytes:
    return _uvarint((field << 3) | wire)


def _varint_field(field: int, v: int) -> bytes:
    return b"" if v == 0 else _tag(field, 0) + _uvarint(v)


def _sfixed64_field(field: int, v: int) -> bytes:
    return b"" if v == 0 else _tag(field, 1) + struct.pack("<q", v)


def _bytes_field(field: int, v: bytes) -> bytes:
    return b"" if not v else _tag(field, 2) + _uvarint(len(v)) + v


def _message_field(field: int, v: bytes) -> bytes:
    return _tag(field, 2) + _uvarint(len(v)) + v


def canonical_vote_bytes(
    chain_id: str,
    height: int,
    round_: int,
    block_hash: bytes,
    parts_total: int,
    parts_hash: bytes,
    timestamp_ns: int,
) -> bytes:
    """CanonicalVote, length-prefixed: type=1 height=2 round=3 (sfixed64)
    block_id=4 {hash=1, part_set_header=2 {total=1, hash=2}} timestamp=5
    {seconds=1, nanos=2} chain_id=6; proto3 zero values are left out."""
    seconds, nanos = divmod(timestamp_ns, NANOS)
    psh = _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    bid = _bytes_field(1, block_hash) + _message_field(2, psh)
    body = (
        _varint_field(1, PRECOMMIT)
        + _sfixed64_field(2, height)
        + _sfixed64_field(3, round_)
        + _message_field(4, bid)
        + _message_field(5, _varint_field(1, seconds) + _varint_field(2, nanos))
        + _bytes_field(6, chain_id.encode())
    )
    return _uvarint(len(body)) + body


@dataclass(frozen=True)
class CommitData:
    """One commit as plain fields. `sigs` has one (flag, timestamp_ns,
    signature) per validator index; `pubkeys`/`powers` are the validator
    set in the same order."""

    chain_id: str
    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    sigs: tuple
    pubkeys: tuple
    powers: tuple


QUORUM = Fraction(2, 3)

_KEYS: dict[bytes, Ed25519PublicKey] = {}


def _key(raw: bytes) -> Ed25519PublicKey:
    """OpenSSL key objects, made once per public key (a committee signs
    every commit of a chain)."""
    k = _KEYS.get(raw)
    if k is None:
        k = _KEYS[raw] = Ed25519PublicKey.from_public_bytes(raw)
    return k


def commit_verdict(c: CommitData, quorum: Fraction = QUORUM) -> tuple[bool, int, int]:
    """(accepted, signatures checked, index of the first bad one or -1).
    Accepted iff every for-block signature met on the way verified and the
    power that signed exactly this block ID passed `quorum` of the total."""
    needed = sum(c.powers) * quorum.numerator // quorum.denominator
    tallied = checked = 0
    for idx, (flag, ts, sig) in enumerate(c.sigs):
        if flag != FLAG_COMMIT:
            continue
        msg = canonical_vote_bytes(
            c.chain_id, c.height, c.round, c.block_hash, c.parts_total,
            c.parts_hash, ts,
        )
        checked += 1
        try:
            _key(c.pubkeys[idx]).verify(sig, msg)
        except (InvalidSignature, ValueError):
            return False, checked, idx
        tallied += c.powers[idx]
        if tallied > needed:
            return True, checked, -1
    return False, checked, -1


def commit_verdicts(commits: list[CommitData], quorum: Fraction = QUORUM,
                    workers: int = 8) -> list[tuple[bool, int, int]]:
    """commit_verdict over many commits. The OpenSSL binding holds the GIL
    (a hundred thousand verifies are ten seconds on one core), so the work
    is shared out to a few child processes running THIS FILE as a script:
    they import the standard library and `cryptography` alone, never jax,
    so the chip stays with its one process. All of them have ended when
    this returns; if one fails, the work is done here instead."""
    workers = min(workers, os.cpu_count() or 1, len(commits) // 16)
    if workers <= 1:
        return [commit_verdict(c, quorum) for c in commits]
    step = -(-len(commits) // workers)
    # plain tuples on the wire: the child knows no package to find a class in
    jobs = [pickle.dumps(([tuple(c.__dict__.values()) for c in commits[i:i + step]], quorum))
            for i in range(0, len(commits), step)]
    procs = [subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                              stdin=subprocess.PIPE, stdout=subprocess.PIPE)
             for _ in jobs]
    try:
        with ThreadPoolExecutor(max_workers=len(procs)) as pool:
            outs = list(pool.map(lambda pj: pj[0].communicate(pj[1], timeout=300)[0],
                                 zip(procs, jobs)))
        if any(p.returncode != 0 for p in procs):
            raise RuntimeError("a reference worker failed")
        return [v for out in outs for v in pickle.loads(out)]
    except (OSError, RuntimeError, subprocess.SubprocessError, pickle.PickleError, EOFError):
        return [commit_verdict(c, quorum) for c in commits]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
            p.wait()


def _leaf(b: bytes) -> bytes:
    return hashlib.sha256(b"\x00" + b).digest()


def _inner(a: bytes, b: bytes) -> bytes:
    return hashlib.sha256(b"\x01" + a + b).digest()


def merkle_root(items: list[bytes]) -> bytes:
    """RFC 6962: split at the largest power of two strictly below n."""
    n = len(items)
    if n == 0:
        return hashlib.sha256(b"").digest()
    if n == 1:
        return _leaf(items[0])
    k = 1
    while k * 2 < n:
        k *= 2
    return _inner(merkle_root(items[:k]), merkle_root(items[k:]))


def kv_state_hash(txs: list[bytes]) -> bytes:
    """App hash of a kvstore that executed `txs` in order: `k=v` sets k,
    a bare tx sets itself; the hash is the merkle root over the sorted
    pairs, each leaf the proto message {1: key, 2: value}."""
    items: dict[bytes, bytes] = {}
    for tx in txs:
        k, v = tx.split(b"=", 1) if b"=" in tx else (tx, tx)
        items[k] = v
    return merkle_root(
        [_bytes_field(1, k) + _bytes_field(2, v) for k, v in sorted(items.items())]
    )


def _worker() -> None:
    """A child of commit_verdicts: one pickled (commits, quorum) on stdin,
    the pickled verdicts on stdout. Only bytes this program wrote are
    unpickled."""
    rows, quorum = pickle.loads(sys.stdin.buffer.read())
    sys.stdout.buffer.write(
        pickle.dumps([commit_verdict(CommitData(*row), quorum) for row in rows]))
    sys.stdout.buffer.flush()


if __name__ == "__main__":
    _worker()
