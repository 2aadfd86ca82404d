"""The plain reference for a committee that changes: which validator set
holds at EVERY height, derived from the genesis set and the chain's own
`val:` transactions, written straight from the specification.

Like `reference.py` (whose vote encoder, OpenSSL verify, > 2/3 tally and
merkle root it uses as they stand) it imports nothing of tendermint_tpu and
takes plain data: public keys as bytes, powers as ints, transactions as
bytes.

    parse_val_tx()   `val:<hex ed25519 key>!<power>` -> (key, power); power
                     0 removes
    derive_sets()    the set of every height: a change carried by block h
                     takes effect at h + 2 (the executor's rule); order is
                     voting power descending, then address ascending;
                     address = SHA-256(key)[:20]; the set's hash is the RFC
                     6962 merkle root over the validators' SimpleValidator
                     proto encodings {1: PublicKey{1: key}, 2: power}
    kv_state_hash()  `reference.kv_state_hash` over the transactions a
                     kvstore keeps: a `val:` transaction is not a key
    plan_end()       where a block-sync plan made at `first` has to end: at
                     the first height whose set is neither the set of
                     `first` nor of `first` + 1 (the two the state holds)
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass

from benchmark import reference as ref

VAL_TX_PREFIX = b"val:"
#: a change in block h is the set of h + EFFECT_DELAY
EFFECT_DELAY = 2


@dataclass(frozen=True)
class ValSet:
    """One validator set in its own order, as plain fields."""

    pubkeys: tuple
    powers: tuple
    hash: bytes


def address(pubkey: bytes) -> bytes:
    return hashlib.sha256(pubkey).digest()[:20]


def parse_val_tx(tx: bytes) -> tuple[bytes, int]:
    key_hex, power = tx[len(VAL_TX_PREFIX):].split(b"!")
    return bytes.fromhex(key_hex.decode()), int(power)


def simple_validator_bytes(pubkey: bytes, power: int) -> bytes:
    return (ref._message_field(1, ref._bytes_field(1, pubkey))
            + ref._varint_field(2, power))


def make_set(powers_by_key: dict[bytes, int]) -> ValSet:
    ordered = sorted(powers_by_key.items(), key=lambda kp: (-kp[1], address(kp[0])))
    return ValSet(
        pubkeys=tuple(k for k, _ in ordered),
        powers=tuple(p for _, p in ordered),
        hash=ref.merkle_root([simple_validator_bytes(k, p) for k, p in ordered]),
    )


def derive_sets(genesis: list[tuple[bytes, int]], txs_at: dict, n_heights: int) -> list:
    """`out[h]` is the validator set of height h, for h in 1..n_heights + 2
    (`out[0]` is None)."""
    current = dict(genesis)
    out = [None, make_set(current)]
    pending: dict[int, list] = {}
    for h in range(1, n_heights + EFFECT_DELAY):
        # the set of h + 1: what h's set becomes under the changes that take
        # effect there
        changes = pending.pop(h + 1, ())
        if changes:
            current = dict(current)
            for key, power in changes:
                if power == 0:
                    del current[key]
                else:
                    current[key] = power
            out.append(make_set(current))
        else:
            out.append(out[-1])
        vals = [parse_val_tx(tx) for tx in txs_at.get(h, ()) if tx.startswith(VAL_TX_PREFIX)]
        if vals:
            pending.setdefault(h + EFFECT_DELAY, []).extend(vals)
    return out


def one_height_stale(sets: list) -> list:
    """The derivation a stale verifier works from: every height is given the
    set of the height before it."""
    return [None] + [sets[max(1, h - 1)] for h in range(1, len(sets))]


def kv_state_hash(txs: list[bytes]) -> bytes:
    return ref.kv_state_hash([tx for tx in txs if not tx.startswith(VAL_TX_PREFIX)])


def plan_end(sets: list, first: int, run_end: int) -> int:
    """The height AFTER the last one a plan made at `first` may hold, given
    blocks in hand up to `run_end` - 1."""
    known = {sets[first].hash, sets[first + 1].hash}
    h = first
    while h < run_end and sets[h].hash in known:
        h += 1
    return h


def expected_plans(sets: list, first: int, n: int) -> list[tuple[int, int]]:
    """(first height, commits) of each verify call a run of `n` blocks from
    `first` needs: planned, cut at a third set, planned again."""
    out = []
    end = first + n
    while first < end:
        stop = plan_end(sets, first, end)
        if stop == first:
            break  # a header the state contradicts: nothing can be planned
        out.append((first, stop - first))
        first = stop
    return out
