"""The seeded light chain of a committee that mixes key types.

`fixtures.light_chain`'s construction, letter for letter, with one
difference: the validator set is `testing.make_validator_set(key_types=...)`,
which deals the key types out by creation index (ed25519, secp256k1,
ed25519, ...) before the set sorts itself by address — BASELINE.json's
config-4 shape.

The set sorts by address, so WHICH keys fall among the first 101 — the ones
a light verifier checks — is a draw: 50.5 ed25519 keys on average, +/- 3 seed
by seed, and a header's cost moves with it by +/- 6% (an ECDSA verify costs
four times an ed25519 one). That is the committee's luck, not the system's
speed, and it would drown what the cell is there to show. So the seeded set
is re-drawn (the seed's bytes and a counter) until the quorum holds the
deal's own expectation, `quorum_mix`: 50 ed25519 + 51 secp256k1 of 101.
Same seed, same set; every seed, the same work a header.

Everything else of a fixture (`LightChain`, `commit_data`,
`corrupt_commit`, `with_corrupt_header`, `seeded_index`) is `fixtures.py`'s
own: a public key goes to the reference as its raw bytes, 32 or 33 of
them, and `reference_mixed` tells the scheme by the length.
"""

from __future__ import annotations

from benchmark import fixtures
from benchmark.fixtures import BASE_TIME_NS, LightChain, _seed_bytes


#: draws of the validator set before giving up (one in seven hits at 150)
MAX_DRAWS = 512


def quorum_mix(n_vals: int, key_types: tuple[str, ...]) -> dict[str, int]:
    """Keys of each type among the first > 2/3 of `n_vals` equal-power
    validators, as the deal expects them: the quorum's size times the
    type's share of the set, rounded down, the rest to the last type."""
    quorum = n_vals * 2 // 3 + 1
    dealt = [key_types[i % len(key_types)] for i in range(n_vals)]
    want = {t: quorum * dealt.count(t) // n_vals for t in dict.fromkeys(key_types)}
    want[key_types[-1]] += quorum - sum(want.values())
    return want


def quorum_rows(vals, quorum: int) -> dict[str, list[int]]:
    """Validator indices of the first `quorum` for-block signatures of a
    commit every validator signed for the block (the first `quorum`
    indices), by the key type that signed them."""
    rows: dict[str, list[int]] = {}
    for idx, v in enumerate(vals.validators[:quorum]):
        rows.setdefault(v.pub_key.TYPE, []).append(idx)
    return rows


def light_chain(seed: int, tag: str, n_headers: int, n_vals: int, power: int,
                key_types: tuple[str, ...]) -> LightChain:
    """`n_headers` hash-linked signed headers over one static validator
    set of `n_vals` keys whose types cycle through `key_types`."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.crypto.hashes import sha256
    from tendermint_tpu.light.types import LightBlock, SignedHeader
    from tendermint_tpu.types.block import BlockID, Header, PartSetHeader

    sb = _seed_bytes(tag, seed)
    chain_id = f"bench-{tag}-{seed}"
    want = quorum_mix(n_vals, key_types)
    for draw in range(MAX_DRAWS):
        vals, keys = tt.make_validator_set(n_vals, power=power, seed=sb + b"/%d" % draw,
                                           key_types=key_types)
        have = quorum_rows(vals, sum(want.values()))
        if {t: len(rows) for t, rows in have.items()} == want:
            break
    else:
        raise RuntimeError(f"seed {seed}: no set with {want} in its quorum in {MAX_DRAWS} draws")
    vh = vals.hash()
    blocks = []
    last_bid = BlockID()
    for h in range(1, n_headers + 1):
        hb = h.to_bytes(8, "big")
        hdr = Header(
            chain_id=chain_id,
            height=h,
            time_ns=BASE_TIME_NS + h * 1_000_000_000,
            last_block_id=last_bid,
            last_commit_hash=sha256(sb + b"lc" + hb),
            data_hash=sha256(sb + b"data" + hb),
            validators_hash=vh,
            next_validators_hash=vh,
            consensus_hash=sha256(b"consensus"),
            app_hash=sha256(sb + b"app" + hb),
            last_results_hash=sha256(b"results"),
            evidence_hash=b"",
            proposer_address=vals.validators[h % n_vals].address,
        )
        bid = BlockID(hdr.hash(), PartSetHeader(1, sha256(sb + b"p" + hb)))
        commit = tt.make_commit(chain_id, h, 0, bid, vals, keys, timestamp_ns=hdr.time_ns)
        blocks.append(LightBlock(SignedHeader(hdr, commit), vals))
        last_bid = bid
    return LightChain(
        chain_id=chain_id,
        vals=vals,
        blocks=blocks,
        now_ns=BASE_TIME_NS + (n_headers + 10) * 1_000_000_000,
        period_ns=10 * 365 * 24 * 3600 * 10**9,
    )


def seeded_bad_index(seed: int, tag: str, rows: list[int], needed: int) -> int:
    """One of `rows` (indices of one key type inside the quorum) drawn from
    the seed among the LAST TENTH the quorum needs, so that a verifier that
    stops short of > 2/3 lets it through; where that tenth holds no row of
    this type, the last one before it."""
    lo = needed - max(1, needed // 10)
    tail = [i for i in rows if i >= lo]
    if not tail:
        return max(rows)
    return tail[fixtures.seeded_index(seed, tag, 0, len(tail) - 1)]
