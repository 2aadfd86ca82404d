"""Seeded fixtures: chains, keys and transactions made from --seed, built
with the program's own types (they are what the program is fed), and the
same data read off as plain fields for the reference.

The builders follow chip_smoke.py's `_blocksync` and the light-client and
block-sync benches of the monolithic bench script PR 29 deleted (copied,
not imported: the yardstick lives under benchmark/). The same seed gives
the same bytes; a `tag` keeps the warm-up chain's chain ID and keys apart
from the window's, so that nothing warmed can be answered from a verdict
cache inside the window.

A block-sync run's fixture is built in a child process
(`harness.FixtureChild`), so what a driver keeps of a chain is what crosses
a pipe cheaply and sits in the measured process's heap as bytes: the wire
blocks and per-height tables (`WireChain`), never a block object a height.
"""

from __future__ import annotations

import dataclasses
import hashlib

from . import reference as ref

BASE_TIME_NS = 1_700_000_000_000_000_000


def _seed_bytes(tag: str, seed: int) -> bytes:
    return b"bench-%s-%d" % (tag.encode(), seed)


def commit_data(chain_id: str, commit, vals) -> ref.CommitData:
    """One commit and its validator set as the reference's plain fields."""
    return ref.CommitData(
        chain_id=chain_id,
        height=commit.height,
        round=commit.round,
        block_hash=commit.block_id.hash,
        parts_total=commit.block_id.part_set_header.total,
        parts_hash=commit.block_id.part_set_header.hash,
        sigs=tuple((cs.flag, cs.timestamp_ns, cs.signature) for cs in commit.signatures),
        pubkeys=tuple(v.pub_key.bytes() for v in vals.validators),
        powers=tuple(v.voting_power for v in vals.validators),
    )


def corrupt_commit(commit, sig_index: int):
    """The same commit with one bit of validator `sig_index`'s signature
    flipped (in the R half, so the encoding stays well-formed)."""
    sigs = list(commit.signatures)
    cs = sigs[sig_index]
    sig = cs.signature
    sigs[sig_index] = dataclasses.replace(
        cs, signature=sig[:7] + bytes([sig[7] ^ 0x10]) + sig[8:]
    )
    return dataclasses.replace(commit, signatures=tuple(sigs))


def seeded_index(seed: int, tag: str, lo: int, hi: int) -> int:
    """A whole number in [lo, hi] drawn from the seed."""
    d = hashlib.sha256(_seed_bytes(tag, seed)).digest()
    return lo + int.from_bytes(d[:8], "big") % (hi - lo + 1)


@dataclasses.dataclass
class LightChain:
    chain_id: str
    vals: object
    blocks: list  # LightBlock at index height-1
    now_ns: int
    period_ns: int

    def commit_data(self, height: int) -> ref.CommitData:
        lb = self.blocks[height - 1]
        return commit_data(self.chain_id, lb.signed_header.commit, lb.validators)


def light_chain(seed: int, tag: str, n_headers: int, n_vals: int, power: int) -> LightChain:
    """`n_headers` hash-linked signed headers over one static validator
    set of `n_vals` ed25519 keys (reference light/client_benchmark_test.go
    shape)."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.crypto.hashes import sha256
    from tendermint_tpu.light.types import LightBlock, SignedHeader
    from tendermint_tpu.types.block import BlockID, Header, PartSetHeader

    sb = _seed_bytes(tag, seed)
    chain_id = f"bench-{tag}-{seed}"
    vals, keys = tt.make_validator_set(n_vals, power=power, seed=sb)
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


def with_corrupt_header(chain: LightChain, height: int, sig_index: int) -> list:
    """The chain's light blocks, with the commit at `height` corrupted."""
    from tendermint_tpu.light.types import LightBlock, SignedHeader

    out = list(chain.blocks)
    lb = out[height - 1]
    bad = corrupt_commit(lb.signed_header.commit, sig_index)
    out[height - 1] = LightBlock(SignedHeader(lb.header, bad), lb.validators)
    return out


class WireChain:
    """What the drivers read of a block-sync chain: the wire bytes the peer
    stand-ins serve and the per-height tables beside them."""

    def block(self, height: int):
        """Block `height`, decoded from its wire bytes as the node's router
        decodes it on arrival."""
        from tendermint_tpu.blocksync import messages as bsm

        return bsm.decode_message(self.wire[height]).block

    def commit(self, height: int):
        """The commit FOR `height`: block height+1's LastCommit; for the
        chain's last height, the commit its builder signed (`head_commit`:
        no later block carries it)."""
        if height == self.n_blocks:
            return self.head_commit
        return self.block(height + 1).last_commit


@dataclasses.dataclass
class KVChain(WireChain):
    chain_id: str
    genesis: object
    vals: object  # the (static) validator set
    n_blocks: int
    app_hash_at: dict  # height -> app hash after executing it
    txs_at: dict  # height -> tuple of raw transactions
    wire: dict  # height -> encoded BlockResponse, as a peer would send it
    block_hash_at: dict  # height -> the block's hash
    head_commit: object  # the commit for height n_blocks

    def commit_data(self, height: int) -> ref.CommitData:
        return commit_data(self.chain_id, self.commit(height), self.vals)


async def fresh_node(genesis):
    """A node's stores and executor at genesis: kvstore ABCI app, MemDB
    block and state stores (the configuration's one cut: no disk)."""
    from tendermint_tpu.abci.kvstore import KVStoreApp
    from tendermint_tpu.consensus.replay import Handshaker
    from tendermint_tpu.proxy import AppConns
    from tendermint_tpu.state.execution import BlockExecutor
    from tendermint_tpu.state.state import state_from_genesis
    from tendermint_tpu.state.store import StateStore
    from tendermint_tpu.store.blockstore import BlockStore
    from tendermint_tpu.store.db import MemDB

    app = KVStoreApp()
    conns = AppConns.local(app)
    bstore, sstore = BlockStore(MemDB()), StateStore(MemDB())
    state = await Handshaker(
        sstore, state_from_genesis(genesis), bstore, genesis
    ).handshake(conns)
    sstore.save(state)
    ex = BlockExecutor(sstore, conns.consensus, block_store=bstore)
    return app, conns, bstore, state, ex


async def kvstore_chain(
    seed: int, tag: str, n_blocks: int, n_vals: int, power: int, txs_per_block: int
) -> KVChain:
    """An `n_blocks` kvstore chain of `n_vals` validators through the real
    executor, `txs_per_block` small seeded transactions a block so that
    the app hash moves, every block committed by the full set."""
    from tendermint_tpu import testing as tt
    from tendermint_tpu.blocksync import messages as bsm
    from tendermint_tpu.state.validation import median_time
    from tendermint_tpu.types.genesis import GenesisDoc, GenesisValidator
    from tendermint_tpu.types.validator_set import ValidatorSet

    chain_id = f"bench-{tag}-{seed}"
    keys = tt.det_priv_keys(n_vals, seed=_seed_bytes(tag, seed))
    genesis = GenesisDoc(
        chain_id=chain_id,
        initial_height=1,
        genesis_time_ns=BASE_TIME_NS,
        validators=[
            GenesisValidator(k.pub_key(), power, f"v{i}") for i, k in enumerate(keys)
        ],
    )
    by_addr = {k.pub_key().address(): k for k in keys}
    app, conns, store, state, ex = await fresh_node(genesis)
    vals: ValidatorSet = state.validators
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
