"""The plain reference against the specification as the program encodes
it, on seeded data: equal sign-bytes, equal verdicts, equal state hash."""

from fractions import Fraction

from benchmark import fixtures
from benchmark import reference as ref


def _chain():
    return fixtures.light_chain(7, "reftest", 6, 10, 10)


def test_canonical_vote_bytes_equal_the_program_s():
    chain = _chain()
    for lb in chain.blocks:
        c = lb.signed_header.commit
        d = chain.commit_data(lb.height)
        for idx in (0, 3, 9):
            flag, ts, _sig = d.sigs[idx]
            assert ref.canonical_vote_bytes(
                d.chain_id, d.height, d.round, d.block_hash, d.parts_total, d.parts_hash, ts
            ) == c.vote_sign_bytes(chain.chain_id, idx)


def test_verdicts():
    chain = _chain()
    d = chain.commit_data(3)
    ok, checked, bad = ref.commit_verdict(d)
    assert (ok, checked, bad) == (True, 7, -1)  # 7 of 10 equal powers pass 2/3
    forged = fixtures.commit_data(
        chain.chain_id,
        fixtures.corrupt_commit(chain.blocks[2].signed_header.commit, 6), chain.vals)
    assert ref.commit_verdict(forged) == (False, 7, 6)
    # a verifier that stops at > 1/2 never reaches signature 6
    assert ref.commit_verdict(forged, Fraction(1, 2))[0] is True
    # a signature past the quorum is never looked at, as in the program
    past = fixtures.commit_data(
        chain.chain_id,
        fixtures.corrupt_commit(chain.blocks[2].signed_header.commit, 8), chain.vals)
    assert ref.commit_verdict(past)[0] is True
    assert [v[0] for v in ref.commit_verdicts([d, forged, d, forged, past])] == [
        True, False, True, False, True]


def test_kv_state_hash_equals_the_app_s():
    from tendermint_tpu.abci.kvstore import _state_hash

    txs = [b"a=1", b"b=2", b"a=3", b"bare", b"k9-1-0=v31"]
    items = {b"a": b"3", b"b": b"2", b"bare": b"bare", b"k9-1-0": b"v31"}
    assert ref.kv_state_hash(txs) == _state_hash(items)
    assert ref.kv_state_hash([]) == _state_hash({})
