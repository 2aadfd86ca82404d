"""The seeded kvstore chain of a committee that mixes key types, for a
block-sync cell: `fixtures_mixed`'s seeded, pinned validator set (75 ed25519
+ 75 secp256k1 dealt by creation index, re-drawn until the quorum holds the
deal's own 50 + 51) under `fixtures.kvstore_chain`'s construction — every
block built and applied through the program's own executor, committed by
the full set — kept as `fixtures.KVChain`: wire bytes and per-height tables
(`FIXTURE = "child"`: it crosses a pipe as bytes, never a block object a
height).

Two differences from `fixtures.kvstore_chain`, and no other: the genesis
validators are the drawn set's (in its own order; a set sorts itself by
address whatever order it is given), and the genesis consensus parameters
admit both key types (`validator.pub_key_types`), as a chain with such a
committee states them.

OpenSSL draws a nonce a signature, so the same seed gives the same keys,
sets, blocks' transactions and verdicts, but other secp256k1 signature
BYTES (and so other block hashes from height 2 on: a block carries its
predecessor's commit). What the same seed reproduces is compared in
`benchmark/tests/test_mixedfull.py`; nothing here or in the driver rests on
the bytes.
"""

from __future__ import annotations

from benchmark import fixtures, fixtures_mixed
from benchmark.fixtures import BASE_TIME_NS, KVChain, _seed_bytes


def pinned_set(seed: int, tag: str, n_vals: int, power: int, key_types: tuple[str, ...]):
    """(validator set, address -> private key): `fixtures_mixed.light_chain`'s
    draw, letter for letter (the seed's bytes and a counter, until the first
    > 2/3 in set order holds `fixtures_mixed.quorum_mix`)."""
    from tendermint_tpu import testing as tt

    sb = _seed_bytes(tag, seed)
    want = fixtures_mixed.quorum_mix(n_vals, key_types)
    for draw in range(fixtures_mixed.MAX_DRAWS):
        vals, keys = tt.make_validator_set(n_vals, power=power, seed=sb + b"/%d" % draw,
                                           key_types=key_types)
        have = fixtures_mixed.quorum_rows(vals, sum(want.values()))
        if {t: len(rows) for t, rows in have.items()} == want:
            return vals, keys
    raise RuntimeError(f"seed {seed}: no set with {want} in its quorum in "
                       f"{fixtures_mixed.MAX_DRAWS} draws")


async def kvstore_chain(seed: int, tag: str, n_blocks: int, n_vals: int, power: int,
                        txs_per_block: int, key_types: tuple[str, ...]) -> KVChain:
    """`fixtures.kvstore_chain` over the pinned mixed set: `n_blocks` kvstore
    blocks through the real executor, `txs_per_block` small seeded
    transactions a block, every block committed by the full set."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.blocksync import messages as bsm
    from tendermint_tpu.state.validation import median_time
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.params import ConsensusParams, ValidatorParams

    chain_id = f"bench-{tag}-{seed}"
    drawn, by_addr = pinned_set(seed, tag, n_vals, power, key_types)
    genesis = GenesisDoc(
        chain_id=chain_id,
        initial_height=1,
        genesis_time_ns=BASE_TIME_NS,
        consensus_params=ConsensusParams(
            validator=ValidatorParams(pub_key_types=tuple(dict.fromkeys(key_types)))),
        validators=[GenesisValidator(v.pub_key, power, f"v{i}")
                    for i, v in enumerate(drawn.validators)],
    )
    app, conns, store, state, ex = await fixtures.fresh_node(genesis)
    vals = state.validators
    if vals.hash() != drawn.hash():
        raise RuntimeError("the node's genesis set is not the drawn one")
    app_hash_at, txs_at, wire, block_hash_at = {}, {}, {}, {}
    commit = None
    try:
        for h in range(1, n_blocks + 1):
            txs = tuple(
                b"k%d-%d-%d=v%d" % (seed, h, j, h * 31 + j) for j in range(txs_per_block)
            )
            time_ns = (
                state.last_block_time_ns
                if h == state.initial_height
                else median_time(commit, state.last_validators)
            )
            block = state.make_block(
                h, txs, commit, (), state.validators.get_proposer().address, time_ns
            )
            parts = block.make_part_set()
            bid = block.block_id(parts.header)
            # commit_verified: this builder signed the LastCommit itself
            state, _ = await ex.apply_block(state, bid, block, commit_verified=True)
            app_hash_at[h] = app.app_hash
            txs_at[h] = txs
            commit = tt.make_commit(
                chain_id, h, 0, bid, state.last_validators, by_addr,
                timestamp_ns=block.header.time_ns + 1,
            )
            store.save_block(block, parts, commit)
            wire[h] = bsm.encode_message(bsm.BlockResponse(block))
            block_hash_at[h] = bid.hash
    finally:
        await conns.stop()
    return KVChain(
        chain_id=chain_id, genesis=genesis, n_blocks=n_blocks,
        app_hash_at=app_hash_at, txs_at=txs_at, wire=wire, vals=vals,
        block_hash_at=block_hash_at, head_commit=commit,
    )
