"""The seeded kvstore chain of a committee that mixes key types, for a
block-sync cell: `fixtures_mixed`'s seeded, pinned validator set (75 ed25519
+ 75 secp256k1 dealt by creation index, re-drawn until the quorum holds the
deal's own 50 + 51) under `fixtures.kvstore_chain`'s construction — every
block built and applied through the program's own executor, committed by
the full set — kept as `fixtures.KVChain`: wire bytes and per-height tables
(`FIXTURE = "child"`: it crosses a pipe as bytes, never a block object a
height).

Three differences from `fixtures.kvstore_chain`, and no other: the genesis
validators are the drawn set's (in its own order; a set sorts itself by
address whatever order it is given), the genesis consensus parameters admit
both key types (`validator.pub_key_types`), as a chain with such a committee
states them — and WHO SIGNS A COMMIT. A secp256k1 signature costs ten times
an ed25519 one (75 of them 35 of a commit's 41 ms on the chip's host, where
`testing.make_commit` signs all 150 in a row: PERF.md §6, PR 44), and a chain
that has to outlast its window is 4,096 blocks, so `CommitSigner` deals a
commit's rows to `SIGN_WORKERS` worker processes (`sign_worker.py`: each holds
the set's private keys and signs with the program's own `PrivKey.sign`) as
soon as the block's ID is known and takes the answers back after the block's
apply — and lays the `Commit` out exactly as `testing.make_commit` does (set
order, validator `i` stamped `timestamp_ns + i`, `CommitSig.for_block`).
Processes, not threads: `cryptography`'s sign keeps the GIL from end to end.
Inside a height, not across heights: block h + 1 carries commit h.

OpenSSL draws a nonce a signature, so the same seed gives the same keys,
sets, blocks' transactions and verdicts, but other secp256k1 signature
BYTES (and so other block hashes from height 2 on: a block carries its
predecessor's commit). What the same seed reproduces is compared in
`benchmark/tests/test_mixedfull.py`; nothing here or in the driver rests on
the bytes.
"""

from __future__ import annotations

import os
import subprocess
import sys

from benchmark import fixtures, fixtures_mixed, harness, sign_worker
from benchmark.fixtures import BASE_TIME_NS, KVChain, _seed_bytes

#: worker processes a chain's builder signs its commits on (PERF.md §6, PR 44:
#: what the chip's host read at 2 to 12)
SIGN_WORKERS = 6


class SignerPool:
    """Worker processes that each hold `keys` — (key type, private key bytes),
    one a validator — and sign the rows dealt to them: `start(msgs)`, one
    message a key, returns at once; `collect()` gives the signatures in the
    keys' order. Every worker gets the same share of each key type (a
    secp256k1 signature costs ten times an ed25519 one), and a share is a few
    dozen rows of ~120 bytes: far under a pipe's buffer, so neither side ever
    waits for the other to read. `close()` ends the workers (the end of their
    stdin) and waits for each; a pool whose owner is killed loses them the
    same way."""

    def __init__(self, keys: list[tuple[str, bytes]], workers: int = SIGN_WORKERS):
        self._dealt = False
        by_type = sorted(range(len(keys)), key=lambda i: keys[i][0])
        workers = max(1, min(workers, len(keys)))
        self._shares = [by_type[w::workers] for w in range(workers)]
        self.procs = [
            subprocess.Popen([sys.executable, os.path.abspath(sign_worker.__file__)],
                             stdin=subprocess.PIPE, stdout=subprocess.PIPE)
            for _ in self._shares]
        for p in self.procs:
            harness.write_part(p.stdin, keys)

    def start(self, msgs: list[bytes]) -> None:
        if self._dealt:
            raise RuntimeError("SignerPool: the last rows were never collected")
        self._dealt = True
        for p, share in zip(self.procs, self._shares):
            harness.write_part(p.stdin, [(i, msgs[i]) for i in share])

    def collect(self) -> list[bytes]:
        sigs = [b""] * sum(map(len, self._shares))
        for p, share in zip(self.procs, self._shares):
            part = sign_worker.read_part(p.stdout)
            if part is None:
                raise RuntimeError(f"SignerPool: worker {p.pid} ended with code {p.wait()}")
            for i, sig in zip(share, part, strict=True):
                sigs[i] = sig
        self._dealt = False
        return sigs

    def close(self) -> None:
        for p in self.procs:
            try:
                p.stdin.close()
            except BrokenPipeError:
                pass  # a worker that died with rows unread: `collect` says so
        for p in self.procs:
            try:
                p.wait(10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
            p.stdout.close()


class CommitSigner:
    """`testing.make_commit` for one static set, in two steps around a block's
    apply: `start` hands the commit's sign-bytes to the pool, `finish` returns
    the `Commit` — round 0, every validator for the block, validator `i`
    stamped `timestamp_ns + i`, in set order."""

    def __init__(self, chain_id: str, vals, by_addr: dict, workers: int = SIGN_WORKERS):
        self.chain_id, self.vals = chain_id, vals
        keys = [by_addr[v.address] for v in vals.validators]
        self.pool = SignerPool([(k.TYPE, k.bytes()) for k in keys], workers)
        self._pending = None

    def start(self, height: int, block_id, timestamp_ns: int) -> None:
        from tendermint_tpu.types.canonical import vote_sign_template
        from tendermint_tpu.types.keys import SignedMsgType

        sign_bytes = vote_sign_template(self.chain_id, SignedMsgType.PRECOMMIT, height, 0, block_id)
        self.pool.start([sign_bytes(timestamp_ns + i) for i in range(len(self.vals.validators))])
        self._pending = (height, block_id, timestamp_ns)

    def finish(self):
        from tendermint_tpu.types.block import Commit, CommitSig

        height, block_id, timestamp_ns = self._pending
        self._pending = None
        return Commit(height, 0, block_id, tuple(
            CommitSig.for_block(v.address, timestamp_ns + i, sig)
            for i, (v, sig) in enumerate(zip(self.vals.validators, self.pool.collect(),
                                             strict=True))))

    def close(self) -> None:
        self.pool.close()


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
    signer = CommitSigner(chain_id, vals, by_addr)
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
            # the workers sign the block's commit under its apply
            signer.start(h, bid, block.header.time_ns + 1)
            # commit_verified: this builder signed the LastCommit itself
            state, _ = await ex.apply_block(state, bid, block, commit_verified=True)
            app_hash_at[h] = app.app_hash
            txs_at[h] = txs
            commit = signer.finish()
            store.save_block(block, parts, commit)
            wire[h] = bsm.encode_message(bsm.BlockResponse(block))
            block_hash_at[h] = bid.hash
    finally:
        signer.close()
        await conns.stop()
    return KVChain(
        chain_id=chain_id, genesis=genesis, n_blocks=n_blocks,
        app_hash_at=app_hash_at, txs_at=txs_at, wire=wire, vals=vals,
        block_hash_at=block_hash_at, head_commit=commit,
    )
