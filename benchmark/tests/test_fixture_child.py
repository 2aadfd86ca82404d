"""Where a cell's fixture is built (`harness.fixture_builder`): a block-sync
driver's by the fixture child (`harness.FixtureChild`, `fixture_child.py`) —
what it hands over is, byte for byte, what the same driver builds in this
process for the same seed, and what crosses of a chain is bytes and tables,
never a block object a height; a light driver's in the measured process
(`harness.FixtureHere`), the builder's own objects with their sharing."""

import importlib

import pytest

from benchmark import harness, run
from benchmark.tests import tiny, tiny_churn, tiny_mesh, tiny_mixed

SEED = 3000003801


def _built(root, cell_name):
    """(the driver, its fixture built in this process by hand, and as
    `harness.fixture_builder` has it built for a run, the builder)."""
    _bench, cell, cfg, _layer = run.load_cell(root, cell_name)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    builder = harness.fixture_builder(driver, root, cell_name, cfg, cell, SEED)
    try:
        theirs = builder.take()
        builder.finish(theirs)
    finally:
        builder.close()
    return driver, harness.assemble(driver.build(cfg, cell, SEED)), theirs, builder


@pytest.mark.parametrize("make_root,cell", [(tiny.make_root, "tinyfull.blocksync"),
                                            (tiny_churn.make_root, tiny_churn.CELL)])
def test_a_block_sync_fixture_crosses_byte_for_byte(tmp_path, make_root, cell):
    driver, mine, theirs, child = _built(make_root(str(tmp_path)), cell)
    assert driver.FIXTURE == "child" and isinstance(child, harness.FixtureChild)
    assert child.waited_s > 0 and child.proc.returncode == 0
    for here, there in ((mine.chain, theirs.chain), (mine.warm, theirs.warm)):
        assert there.wire == here.wire and len(there.wire) == there.n_blocks
        assert there.app_hash_at == here.app_hash_at and there.txs_at == here.txs_at
        assert there.block_hash_at == here.block_hash_at
        assert there.genesis.to_json() == here.genesis.to_json()
        assert there.head_commit.encode() == here.head_commit.encode()
        assert there.commit_data(there.n_blocks) == here.commit_data(here.n_blocks)
        assert there.commit_data(7) == here.commit_data(7)
        assert there.block(9).encode() == here.block(9).encode()
        assert there.block(9).hash() == there.block_hash_at[9]
        # no source store, no block object: bytes and tables alone
        assert getattr(there, "store", None) is None and getattr(here, "store", None) is None
        assert all(type(v) is bytes for v in there.wire.values())
    skip = {"chain", "warm", "observed", "hub", "warm_stale_commit"}
    for k in set(vars(mine)) - skip:
        assert getattr(mine, k) == getattr(theirs, k), k
    if cell == tiny_churn.CELL:
        assert theirs.warm_stale_commit.encode() == mine.warm_stale_commit.encode()
        assert theirs.chain.sets == mine.chain.sets and theirs.chain.changes == mine.chain.changes
        assert theirs.chain.keys is None and theirs.chain.set_objs is None


def _sigs_by_scheme(lb, deterministic: bool):
    """A light block's signatures whose bytes a seed pins: ed25519 signing is
    deterministic, OpenSSL's ECDSA draws a fresh nonce a signature (the mixed
    chain's secp256k1 rows differ from build to build, in one process too)."""
    return [cs.signature for cs, v in zip(lb.signed_header.commit.signatures,
                                          lb.validators.validators)
            if (v.pub_key.TYPE == "ed25519") is deterministic]


@pytest.mark.parametrize("make_root,cell", [(tiny.make_root, "tinylight.sequential"),
                                            (tiny_mixed.make_root, tiny_mixed.CELL),
                                            (tiny_mesh.make_root, tiny_mesh.CELL)])
def test_a_light_fixture_is_built_in_the_measured_process(tmp_path, make_root, cell):
    """No child, no pickle: a process that revived a light chain ran the light
    client 2-7% slower than one that built it (PERF.md §6, PR 38), so the
    window walks the builder's own objects, as before PR 38."""
    driver, mine, theirs, builder = _built(make_root(str(tmp_path)), cell)
    assert getattr(driver, "FIXTURE", "here") == "here"
    assert isinstance(builder, harness.FixtureHere) and builder.waited_s == 0.0
    for here, there in ((mine.chain, theirs.chain), (mine.warm, theirs.warm)):
        if cell == tiny_mixed.CELL:
            for a, b in zip(there.blocks, here.blocks, strict=True):
                assert a.header.encode() == b.header.encode()
                assert a.validators.hash() == b.validators.hash()
                assert a.signed_header.commit.block_id == b.signed_header.commit.block_id
                assert _sigs_by_scheme(a, True) == _sigs_by_scheme(b, True)
                assert len(_sigs_by_scheme(a, False)) == len(_sigs_by_scheme(b, False)) > 0
        else:
            assert [lb.encode() for lb in there.blocks] == [lb.encode() for lb in here.blocks]
        assert (there.chain_id, there.now_ns, there.period_ns) == (
            here.chain_id, here.now_ns, here.period_ns)
        # ONE validator-set object under every light block, as the builder made it
        assert all(lb.validators is there.vals for lb in there.blocks)
    for k in set(vars(mine)) - {"chain", "warm", "observed"}:
        assert getattr(mine, k) == getattr(theirs, k), k


def test_a_chain_made_by_hand_from_a_store_reads_as_one_built_with_its_wire():
    """`ChurnChain.__post_init__`: tier-1's hand-made cases give a source
    store and no wire bytes; everything reads the wire."""
    import asyncio

    from benchmark import fixtures_churn

    chain = asyncio.run(fixtures_churn.churn_chain(SEED, "hand", 12, 7, 10, 2, 4, 4))
    by_hand = type(chain)(**{**{f: getattr(chain, f) for f in chain.__dataclass_fields__},
                             "wire": {}, "head_commit": None, "block_hash_at": {}})
    assert by_hand.wire == chain.wire and len(chain.wire) == 12
    for h in range(1, 13):
        assert by_hand.commit(h).encode() == chain.commit(h).encode()
        assert chain.block(h).encode() == chain.store.load_block(h).encode()
        assert chain.store.load_block_meta(h).block_id.hash == chain.block_hash_at[h]
    shed = chain.shed()
    assert shed.store is None and shed.keys is None and shed.commit_data(7) == chain.commit_data(7)


def test_a_fixture_that_is_not_there_yet_is_not_waited_for(tmp_path):
    """`take(block=False)`: run.py asks so while the probe thread still runs,
    so that `fixture_wait_s` holds only seconds that delayed the window."""
    root = tiny.make_root(str(tmp_path))
    child = harness.FixtureChild(root, "tinyfull.blocksync", SEED)
    try:
        assert child.take(block=False) is None and child.waited_s == 0.0  # still importing
        fx = child.take()
        assert fx.chain is None and child.waited_s > 0
        child.finish(fx)
        assert fx.chain.n_blocks == tiny.BLOCKS
    finally:
        child.close()


def test_assemble_fills_in_what_a_build_yields_late():
    class Fx:
        def __init__(self):
            self.warm, self.chain = "w", None

    fx = Fx()
    assert harness.assemble(iter([fx, {"chain": "c"}])) is fx and (fx.warm, fx.chain) == ("w", "c")
    assert harness.assemble(iter([fx])) is fx


def test_a_child_that_fails_says_so(tmp_path):
    root = tiny.make_root(str(tmp_path))
    child = harness.FixtureChild(root, "no.such.cell", SEED)
    try:
        with pytest.raises(RuntimeError, match="fixture child ended with code"):
            child.take()
    finally:
        child.close()
