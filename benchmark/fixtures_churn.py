"""Seeded kvstore chains whose committee CHANGES: `fixtures.kvstore_chain`
with a schedule of `val:` transactions, built through the program's own
executor (a chain is what the program is fed), and the same data read off as
plain fields for the reference — each commit beside the validator set that
`reference_churn` derives for its height, never the program's.

The schedule (`configs/churn150.json` `rotation`): every `period`-th height
carries a change beside the block's kvstore transactions, effective two
heights later. Every `swap_every`-th change is a SWAP in that one block (the
last validator in set order to power 0, a fresh seeded ed25519 key in at the
base power: two `val:` transactions); the others are POWER changes (one
`val:` transaction: a sitting validator drawn from the seed goes from the
base power to base + 1, or back if it holds that). The count of validators
never moves, and the changes fall at the same heights for every seed.
"""

from __future__ import annotations

import dataclasses

from . import fixtures
from . import reference as ref
from . import reference_churn as refc


@dataclasses.dataclass
class ChurnChain(fixtures.WireChain):
    chain_id: str
    genesis: object
    vals: object  # the GENESIS validator set (heights 1 .. period + 1)
    store: object  # the builder's source BlockStore (tier-1 tests read it); None once shed
    n_blocks: int
    app_hash_at: dict  # height -> app hash after executing it
    txs_at: dict  # height -> tuple of raw transactions, `val:` ones included
    wire: dict  # height -> encoded BlockResponse, as a peer would send it
    sets: list  # height -> reference_churn.ValSet (the REFERENCE's derivation)
    changes: dict  # height that carries a change -> "power" | "swap"
    keys: dict  # address -> private key, of every validator that ever sat; None once shed
    set_hash_at: dict  # height -> hash of the PROGRAM's set of that height
    set_objs: dict  # that hash -> the program's ValidatorSet (to sign with); None once shed
    block_hash_at: dict = dataclasses.field(default_factory=dict)  # height -> the block's hash
    head_commit: object = None  # the commit for height n_blocks

    def __post_init__(self):
        # a chain made BY HAND from a source store alone, as tier-1's
        # `tests/test_blocksync_rotation.py` makes its cases (a file no
        # benchmark PR may edit; PERF.md §7): the bytes everything here reads
        if not self.wire and self.store is not None:
            from tendermint_tpu.blocksync import messages as bsm

            self.wire = {h: bsm.encode_message(bsm.BlockResponse(self.store.load_block(h)))
                         for h in range(1, self.n_blocks + 1)}
            self.head_commit = self.store.load_seen_commit(self.n_blocks)

    def shed(self):
        """Without what only the builder's process can hold or use: the
        source store, the private keys (no pickle takes them) and the
        program's set objects they sign under. `stale_commit` is for before."""
        return dataclasses.replace(self, store=None, keys=None, set_objs=None)

    def commit_data(self, height: int, commit=None, sets=None) -> ref.CommitData:
        """The commit FOR `height` (carried by block height+1 as its
        LastCommit) beside the reference's validator set of that height."""
        if commit is None:
            commit = self.commit(height)
        vs = (sets or self.sets)[height]
        return ref.CommitData(
            chain_id=self.chain_id,
            height=commit.height,
            round=commit.round,
            block_hash=commit.block_id.hash,
            parts_total=commit.block_id.part_set_header.total,
            parts_hash=commit.block_id.part_set_header.hash,
            sigs=tuple((cs.flag, cs.timestamp_ns, cs.signature) for cs in commit.signatures),
            pubkeys=vs.pubkeys,
            powers=vs.powers,
        )

    def first_heights_of_power_sets(self) -> list[int]:
        """Heights at which a set made by a POWER change first holds."""
        return [h + refc.EFFECT_DELAY for h, kind in sorted(self.changes.items())
                if kind == "power" and h + refc.EFFECT_DELAY < self.n_blocks]

    def stale_commit(self, height: int):
        """A commit FOR `height` signed, in order, by every validator of the
        set of the height BEFORE: valid under that stale set, and under the
        true one only where the two agree position for position."""
        from tendermint_tpu import testing as tt

        block, honest = self.block(height), self.commit(height)
        stale = self.set_objs[self.set_hash_at[height - 1]]
        return tt.make_commit(
            self.chain_id, height, 0, honest.block_id, stale, self.keys,
            timestamp_ns=block.header.time_ns + 1,
        )


def stale_commit_is_telling(chain: ChurnChain, height: int) -> bool:
    """Whether the reference ACCEPTS `chain.stale_commit(height)` under the
    set of the height before and REFUSES it under the set of `height`: true
    wherever the change moved a validator among the positions the quorum
    reads (a mover that already sat first keeps them all)."""
    forged = chain.stale_commit(height)
    stale = refc.one_height_stale(chain.sets)
    return (ref.commit_verdict(chain.commit_data(height, forged, stale))[0]
            and not ref.commit_verdict(chain.commit_data(height, forged))[0])


def _val_tx(pub_key, power: int) -> bytes:
    return b"val:" + pub_key.bytes().hex().encode() + b"!%d" % power


async def churn_chain(
    seed: int, tag: str, n_blocks: int, n_vals: int, power: int, txs_per_block: int,
    period: int, swap_every: int,
) -> ChurnChain:
    """An `n_blocks` kvstore chain of `n_vals` validators through the real
    executor, every block committed by the full set OF ITS HEIGHT, with the
    schedule above."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.blocksync import messages as bsm
    from tendermint_tpu.state.validation import median_time
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator

    chain_id = f"bench-{tag}-{seed}"
    sb = fixtures._seed_bytes(tag, seed)
    keys = tt.det_priv_keys(n_vals, seed=sb)
    genesis = GenesisDoc(
        chain_id=chain_id,
        initial_height=1,
        genesis_time_ns=fixtures.BASE_TIME_NS,
        validators=[
            GenesisValidator(k.pub_key(), power, f"v{i}") for i, k in enumerate(keys)
        ],
    )
    by_addr = {k.pub_key().address(): k for k in keys}
    app, conns, store, state, ex = await fixtures.fresh_node(genesis)
    genesis_vals = state.validators
    app_hash_at, txs_at, wire, changes, block_hash_at = {}, {}, {}, {}, {}
    set_hash_at, set_objs = {}, {}
    commit = None
    try:
        for h in range(1, n_blocks + 1):
            txs = [b"k%d-%d-%d=v%d" % (seed, h, j, h * 31 + j) for j in range(txs_per_block)]
            if h % period == 0:
                k = h // period
                # the newest set the chain knows: pending changes included
                sitting = state.next_validators.validators
                if k % swap_every == 0:
                    joiner = tt.det_priv_keys(1, seed=sb + b"-join-%d" % k)[0]
                    by_addr[joiner.pub_key().address()] = joiner
                    txs += [_val_tx(sitting[-1].pub_key, 0), _val_tx(joiner.pub_key(), power)]
                    changes[h] = "swap"
                else:
                    mover = sitting[fixtures.seeded_index(seed, f"{tag}-mover-{k}", 0,
                                                          len(sitting) - 1)]
                    to = power + 1 if mover.voting_power == power else power
                    txs.append(_val_tx(mover.pub_key, to))
                    changes[h] = "power"
            txs = tuple(txs)
            time_ns = (
                state.last_block_time_ns
                if h == state.initial_height
                else median_time(commit, state.last_validators)
            )
            set_hash_at[h] = state.validators.hash()
            set_objs.setdefault(set_hash_at[h], state.validators)
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
    sets = refc.derive_sets(
        [(k.pub_key().bytes(), power) for k in keys], txs_at, n_blocks)
    return ChurnChain(
        chain_id=chain_id, genesis=genesis, vals=genesis_vals, store=store,
        n_blocks=n_blocks, app_hash_at=app_hash_at, txs_at=txs_at, wire=wire,
        block_hash_at=block_hash_at, head_commit=commit, sets=sets, changes=changes, keys=by_addr, set_hash_at=set_hash_at,
        set_objs=set_objs,
    )
