"""The plain reference for a full node catching up on a chain whose committee
mixes key types: `reference_mixed.py`'s rule for a commit — every signature
under ITS OWN key's scheme (a 32-byte key is ed25519: OpenSSL's verify; a
33-byte key is secp256k1: ECDSA over SHA-256, r || s parsed by hand,
0 < r < n, 0 < s <= n/2), `needed` = the first rows in set order whose power
passes > 2/3 — applied to what `reference.py`'s block-sync comparison holds a
sync to: a verdict per commit, every stored block's hash, heights applied 1,
2, 3 ..., the kvstore app hash over the applied transactions.

It imports the standard library, `reference.py` (the kvstore merkle hash and
`CommitData`) and `reference_mixed.py` (the commit rule and its child
processes), as they are: plain data in, plain numbers out, nothing of
tendermint_tpu. What the drivers hand it is read off the seeded fixture and
the node's stores as bytes, ints and tuples.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from benchmark.reference import CommitData, kv_state_hash  # noqa: F401  (CommitData: the input's type)
from benchmark.reference_mixed import (  # noqa: F401  (commit_verdict: for one commit)
    ED25519,
    SECP256K1,
    commit_verdict,
    commit_verdicts,
)

SCHEMES = (ED25519, SECP256K1)


@dataclass
class RangeReading:
    """What the reference says of the verify calls a sync made."""

    attempted: int = 0  # commits the calls consumed
    failed: int = 0  # commits the PROGRAM refused
    mismatches: int = 0  # verdicts that differ from the reference's
    needed: int = 0  # signatures the > 2/3 rule needs, over all calls
    #: per call, in the order given: {scheme: rows the rule needs}
    by_call: list = field(default_factory=list)

    def needed_of(self, scheme: str) -> int:
        return sum(call[scheme] for call in self.by_call)


def read_ranges(commit_data_at, ranges) -> RangeReading:
    """`ranges`: (first height, commits, index the program refused or None)
    per verify call. `commit_data_at(h)` is the commit FOR height h as
    `CommitData`. A call the program accepted holds only commits the
    reference accepts; one it refused at index i consumed the commits up to
    i, and the reference refuses exactly that one."""
    heights = sorted({h for first, n, _f in ranges for h in range(first, first + n)})
    verdicts = dict(zip(heights, commit_verdicts([commit_data_at(h) for h in heights])))
    out = RangeReading()
    for first, n, failed_index in ranges:
        call = dict.fromkeys(SCHEMES, 0)
        for i in range(n):
            ok, checked, _bad, by_scheme = verdicts[first + i]
            out.attempted += 1
            out.needed += checked
            for scheme in SCHEMES:
                call[scheme] += by_scheme[scheme]
            if failed_index is None:
                out.mismatches += not ok
            elif i == failed_index:
                out.failed += 1
                out.mismatches += ok
        out.by_call.append(call)
    return out


def apply_order_faults(applied: list, final_height: int) -> int:
    """Heights applied strictly 1, 2, 3 ..., as many as the store holds."""
    return (sum(1 for i, h in enumerate(applied) if h != i + 1)
            + abs(len(applied) - final_height))


def stored_mismatches(stored_hashes: dict, block_hash_at: dict, final_height: int) -> int:
    """Every stored block's hash against the chain's."""
    return sum(1 for h in range(1, final_height + 1)
               if stored_hashes.get(h) != block_hash_at[h])


def app_hash_mismatch(app_hash: bytes, txs_at: dict, final_height: int,
                      chain_app_hash: bytes | None) -> int:
    """The node's app hash, and the chain's own at that height, against a
    plain kvstore merkle hash of the transactions applied."""
    want = kv_state_hash([tx for h in range(1, final_height + 1) for tx in txs_at[h]])
    return int(app_hash != want) + int(chain_app_hash is not None and chain_app_hash != want)


def first_refused(commits: list) -> int:
    """Index of the first commit of `commits` the reference refuses; -1
    where it accepts them all."""
    return next((i for i, c in enumerate(commits) if not commit_verdict(c)[0]), -1)
