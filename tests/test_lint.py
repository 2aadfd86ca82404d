"""tmtlint — the AST invariant analyzer suite (tendermint_tpu/tools/lint).

Every rule gets a positive fixture (the exact pattern it exists to
catch) and a negative one (the disciplined version must stay clean),
pragma-suppression semantics are pinned, and the whole-tree run is the
tier-1 gate: the repo itself must lint clean, fast enough not to eat
the suite's budget.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from tendermint_tpu.tools.lint import (
    ALL_RULES,
    BAD_PRAGMA,
    DEFAULT_ALLOWLIST,
    RULES_BY_ID,
    Allowlist,
    lint_source,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a rel path inside every strict-profile scope (consensus is covered by
#: clock-discipline and nondeterminism; scope-specific tests override)
NODE_PATH = "tendermint_tpu/consensus/somefile.py"


def run(src: str, rule_id: str, rel: str = NODE_PATH, allowlist=None):
    """Single-rule findings for an inline fixture."""
    out = lint_source(
        textwrap.dedent(src), rel, [RULES_BY_ID[rule_id]], allowlist
    )
    return [f for f in out if f.rule == rule_id]


def run_all(src: str, rel: str = NODE_PATH, allowlist=None):
    return lint_source(textwrap.dedent(src), rel, ALL_RULES, allowlist)


# ---------------------------------------------------------------------------
# blocking-in-async


def test_blocking_sleep_in_async_flagged():
    src = """
    import time
    async def worker():
        time.sleep(1.0)
    """
    fs = run(src, "blocking-in-async")
    assert len(fs) == 1 and fs[0].line == 4


def test_async_sleep_and_sync_sleep_clean():
    src = """
    import asyncio, time
    async def worker():
        await asyncio.sleep(1.0)
    def sync_worker():
        time.sleep(1.0)
    """
    assert run(src, "blocking-in-async") == []


def test_nested_sync_def_is_its_own_context():
    # the nested def runs via to_thread — blocking there is the FIX
    src = """
    import time, asyncio
    async def worker():
        def heavy():
            time.sleep(1.0)
        await asyncio.to_thread(heavy)
    """
    assert run(src, "blocking-in-async") == []


def test_raw_open_and_result_in_async_flagged():
    src = """
    async def worker(fut):
        with open("x") as f:
            data = f.read()
        return fut.result()
    """
    assert {f.line for f in run(src, "blocking-in-async")} == {3, 5}


def test_fs_layer_open_in_async_clean():
    src = """
    async def worker(self):
        with self.fs.open("x", "ab") as f:
            pass
    """
    assert run(src, "blocking-in-async") == []


def test_from_import_and_alias_cannot_evade():
    # `from time import sleep` / `import time as t` resolve through the
    # file's import table — renaming is not an escape hatch
    src = """
    from time import sleep
    import time as t
    async def worker():
        sleep(1.0)
        t.sleep(1.0)
    """
    assert {f.line for f in run(src, "blocking-in-async")} == {5, 6}


def test_from_import_cannot_evade_clock_and_random_rules():
    src = """
    from time import monotonic
    from random import choice
    def deadline():
        return monotonic() + 5.0
    def pick(peers):
        return choice(peers)
    """
    assert len(run(src, "clock-discipline", rel="tendermint_tpu/blocksync/x.py")) == 1
    assert len(run(src, "nondeterminism", rel="tendermint_tpu/p2p/x.py")) == 1


def test_subprocess_in_async_flagged():
    src = """
    import subprocess
    async def worker():
        subprocess.run(["ls"])
    """
    assert len(run(src, "blocking-in-async")) == 1


def test_blocking_relaxed_for_tests_profile():
    src = """
    import time
    async def helper():
        time.sleep(0.1)
    """
    assert run(src, "blocking-in-async", rel="tests/test_x.py") == []


# ---------------------------------------------------------------------------
# absorbed-cancellation


def test_bare_except_without_reraise_flagged():
    src = """
    async def loop():
        try:
            await work()
        except:
            cleanup()
    """
    fs = run(src, "absorbed-cancellation")
    assert len(fs) == 1 and "bare" in fs[0].message


def test_base_exception_with_reraise_clean():
    src = """
    async def loop():
        try:
            await work()
        except BaseException:
            cleanup()
            raise
    """
    assert run(src, "absorbed-cancellation") == []


def test_swallowed_cancelled_error_flagged_and_reraise_clean():
    bad = """
    import asyncio
    async def loop():
        try:
            await work()
        except asyncio.CancelledError:
            cleanup()
    """
    good = bad + "            raise\n"
    assert len(run(bad, "absorbed-cancellation")) == 1
    assert run(good, "absorbed-cancellation") == []


def test_cancelled_in_tuple_flagged():
    src = """
    import asyncio
    async def loop():
        try:
            await work()
        except (ConnectionError, asyncio.CancelledError):
            pass
    """
    assert len(run(src, "absorbed-cancellation")) == 1


def test_silent_except_exception_around_await_flagged():
    bad = """
    async def loop(self):
        try:
            await work()
        except Exception:
            pass
    """
    good = """
    async def loop(self):
        try:
            await work()
        except Exception as e:
            self.logger.debug("dropped: %r", e)
    """
    assert len(run(bad, "absorbed-cancellation")) == 1
    assert run(good, "absorbed-cancellation") == []


def test_unshielded_wait_for_in_cleanup_flagged():
    bad = """
    import asyncio
    async def stop(self):
        try:
            await self.run()
        finally:
            await asyncio.wait_for(self.drain(), 1.0)
    """
    good = """
    import asyncio
    async def stop(self):
        try:
            await self.run()
        finally:
            await asyncio.wait_for(asyncio.shield(self.drain()), 1.0)
    """
    fs = run(bad, "absorbed-cancellation")
    assert len(fs) == 1 and "shield" in fs[0].message
    assert run(good, "absorbed-cancellation") == []


def test_raise_inside_nested_def_is_not_a_reraise():
    # a `raise` in a nested callback runs in a different frame — the
    # handler itself still swallows the cancellation
    src = """
    async def loop():
        try:
            await work()
        except BaseException:
            def on_done():
                raise RuntimeError("nested")
            register(on_done)
    """
    assert len(run(src, "absorbed-cancellation")) == 1


def test_sync_function_bare_except_not_this_rules_business():
    src = """
    def loop():
        try:
            work()
        except:
            pass
    """
    assert run(src, "absorbed-cancellation") == []


def test_absorbed_cancellation_applies_to_tests_profile():
    src = """
    import asyncio
    async def helper():
        try:
            await work()
        except asyncio.CancelledError:
            pass
    """
    assert len(run(src, "absorbed-cancellation", rel="tests/test_x.py")) == 1


# ---------------------------------------------------------------------------
# task-leak


def test_dropped_create_task_flagged():
    src = """
    import asyncio
    async def fire(self):
        asyncio.get_running_loop().create_task(self.work())
        asyncio.ensure_future(self.work())
    """
    assert {f.line for f in run(src, "task-leak")} == {4, 5}


def test_tracked_task_clean():
    src = """
    import asyncio
    async def fire(self):
        t = asyncio.create_task(self.work())
        self._tasks.append(asyncio.create_task(self.work()))
        self.spawn(self.work())
        return t
    """
    assert run(src, "task-leak") == []


# ---------------------------------------------------------------------------
# clock-discipline


def test_wall_clock_in_consensus_flagged():
    src = """
    import time
    def vote_time():
        return time.time_ns()
    def deadline():
        return time.monotonic() + 5.0
    """
    assert {f.line for f in run(src, "clock-discipline")} == {4, 6}


def test_injected_clock_clean():
    src = """
    def vote_time(self):
        return self.clock.now_ns()
    def deadline(self):
        return self.clock.monotonic() + 5.0
    """
    assert run(src, "clock-discipline") == []


def test_clock_rule_scoped_to_consensus_adjacent_dirs():
    src = """
    import time
    def stamp():
        return time.time()
    """
    # libs/ (e.g. flowrate meters) and crypto/ are out of scope
    assert run(src, "clock-discipline", rel="tendermint_tpu/libs/flowrate.py") == []
    assert len(run(src, "clock-discipline", rel="tendermint_tpu/blocksync/x.py")) == 1
    assert len(run(src, "clock-discipline", rel="tendermint_tpu/statesync/x.py")) == 1


# ---------------------------------------------------------------------------
# verify-chokepoint


def test_direct_verify_signature_flagged():
    src = """
    def check(pk, msg, sig):
        return pk.verify_signature(msg, sig)
    """
    fs = run(src, "verify-chokepoint", rel="tendermint_tpu/types/vote.py")
    assert len(fs) == 1 and "VerifyHub" in fs[0].message


def test_verify_signature_interface_def_clean():
    src = """
    class PubKey:
        def verify_signature(self, msg, sig):
            raise NotImplementedError
    """
    assert run(src, "verify-chokepoint", rel="tendermint_tpu/types/keys.py") == []


def test_sync_facade_in_coroutine_flagged():
    """The pipelined ingest made the hub's SYNC facade inside a
    coroutine a lint error in consensus/blocksync/statesync: it blocks
    the event loop per signature and pins batch occupancy at 1."""
    src = """
    async def handle(self, vote):
        ok = self.hub.verify_sync(pk, msg, sig)
        ok2 = self.hub.submit_nowait(pk, msg, sig).result(5.0)
    """
    fs = run(src, "verify-chokepoint", rel="tendermint_tpu/consensus/ingest.py")
    assert len(fs) == 2
    assert "blocks the event loop" in fs[0].message
    assert "sync facade in disguise" in fs[1].message
    # same pattern in blocksync is equally flagged
    assert len(run(src, "verify-chokepoint", rel="tendermint_tpu/blocksync/pool.py")) == 2


def test_sync_facade_clean_cases():
    # sync defs may block (the evidence pool, replay); the async hub API
    # is the blessed path; .result() on other receivers is untouched
    src = """
    def sync_check(self, pk, msg, sig):
        return self.hub.verify_sync(pk, msg, sig)
    async def pipelined(self, pk, msg, sig):
        return await self.hub.verify(pk, msg, sig)
    async def other_future(self):
        return self.pool.submit(job).result()
    """
    assert run(src, "verify-chokepoint", rel="tendermint_tpu/consensus/state.py") == []
    # outside consensus/blocksync/statesync the facade stays legal (the
    # evidence pool and validation shim are synchronous by design)
    flagged = """
    async def handle(self):
        return self.hub.verify_sync(pk, msg, sig)
    """
    assert run(flagged, "verify-chokepoint", rel="tendermint_tpu/types/validation.py") == []


def test_sync_facade_pragma_escape_hatch():
    src = """
    async def handle(self):
        return self.hub.verify_sync(pk, msg, sig)  # tmtlint: allow[verify-chokepoint] -- measured: cache hit path only
    """
    assert run(src, "verify-chokepoint", rel="tendermint_tpu/consensus/state.py") == []


def test_sync_facade_flagged_in_mempool_and_rpc():
    """TxIngress put mempool/ and rpc/ on the flood-facing event loop:
    the sync hub facade (and direct verify) is a defect there too."""
    src = """
    async def admit(self, tx):
        ok = self.hub.verify_sync(pk, msg, sig)
    """
    assert len(run(src, "verify-chokepoint", rel="tendermint_tpu/mempool/ingress.py")) == 1
    assert len(run(src, "verify-chokepoint", rel="tendermint_tpu/rpc/core.py")) == 1


def test_bls_funnel_calls_flagged_outside_crypto():
    """The aggregate-commit path must not grow a second verify funnel:
    direct pairing / aggregate-verify calls outside crypto/ bypass the
    hub's verdict cache and the breaker-guarded device routing."""
    src = """
    def check_commit(self, pubs, msgs, agg):
        if not bls.aggregate_verify(pubs, msgs, agg):
            raise ValueError("bad aggregate")
    def raw_pairing(self, p, q):
        return bls_math.pairing(p, q)
    def kernel_direct(self, items):
        return bls_pairing.verify_pairs_batch(items, pad_to=4)
    """
    fs = run(src, "verify-chokepoint", rel="tendermint_tpu/types/validation.py")
    assert len(fs) == 3
    assert all("second verify funnel" in f.message for f in fs)
    # blocksync is equally fenced
    assert len(run(src, "verify-chokepoint", rel="tendermint_tpu/blocksync/pool.py")) == 3


def test_bls_funnel_clean_cases():
    # the hub chokepoint itself, PoP checks (construction-time), and
    # aggregation (not verification) all stay legal outside crypto/
    src = """
    def check_commit(self, pubs, msgs, agg):
        return verify_aggregate(pubs, msgs, agg)
    def check_pop(self, gv):
        return gv.pub_key.pop_verify(gv.pop)
    def make_aggregate(self, sigs):
        return bls.aggregate_signatures(sigs)
    """
    assert run(src, "verify-chokepoint", rel="tendermint_tpu/types/validation.py") == []
    # inside crypto/ the primitives ARE the chokepoint (allowlisted)
    direct = """
    def verify(self, pubs, msgs, agg):
        return bls_math.aggregate_verify(pubs, msgs, agg)
    """
    assert (
        run(
            direct,
            "verify-chokepoint",
            rel="tendermint_tpu/crypto/bls.py",
            allowlist=Allowlist.load(DEFAULT_ALLOWLIST),
        )
        == []
    )


def test_verifyd_funnel_calls_flagged_outside_crypto():
    """crypto/verifyd is the ONLY legal raw-socket verify path: a call
    site talking to the sidecar directly skips the hub's verdict cache,
    lanes, AND the breaker's inline-local fallback — a daemon crash
    there becomes a liveness event instead of a degrade."""
    src = """
    def fast_verify(self, items):
        client = client_for(self.sock_path)
        return client.remote_verify_batch(items)
    def agg(self, pubs, msgs, sig):
        return verifyd.VerifydClient(self.sock).remote_verify_aggregate(pubs, msgs, sig)
    """
    fs = run(src, "verify-chokepoint", rel="tendermint_tpu/blocksync/pool.py")
    assert len(fs) == 4  # client_for + remote_verify_batch + ctor + agg
    assert all("raw-socket verify path" in f.message for f in fs)
    # consensus is equally fenced
    assert len(run(src, "verify-chokepoint", rel="tendermint_tpu/consensus/state.py")) == 4


def test_verifyd_funnel_clean_cases():
    # the hub route (config knob) and diagnostics stay legal outside
    # crypto/; inside crypto/ the client IS the chokepoint (allowlisted)
    src = """
    def build_hub(self, cfg):
        return VerifyHub(verifyd_sock=cfg.verifyd_sock)
    def diagnostics(self, client):
        return client.remote_stats()
    """
    assert run(src, "verify-chokepoint", rel="tendermint_tpu/node.py") == []
    direct = """
    def route(self, batch):
        return client_for(self.verifyd_sock).remote_verify_batch(batch)
    """
    assert (
        run(
            direct,
            "verify-chokepoint",
            rel="tendermint_tpu/crypto/verify_hub.py",
            allowlist=Allowlist.load(DEFAULT_ALLOWLIST),
        )
        == []
    )


# ---------------------------------------------------------------------------
# hash-chokepoint


def test_raw_sha256_flagged_in_hash_hot_paths():
    """ISSUE 20: raw hashlib in types/state/consensus/mempool/light
    bypasses the HashHub (lane stats, metrics, device batching)."""
    src = """
    import hashlib
    def tx_key(tx):
        return hashlib.sha256(tx).digest()
    """
    for rel in (
        "tendermint_tpu/types/tx.py",
        "tendermint_tpu/state/execution.py",
        "tendermint_tpu/consensus/state.py",
        "tendermint_tpu/mempool/pool.py",
        "tendermint_tpu/light/client.py",
    ):
        fs = run(src, "hash-chokepoint", rel=rel)
        assert len(fs) == 1 and "HashHub" in fs[0].message, rel


def test_sha256_via_import_alias_and_relative_import_flagged():
    # resolve_call canonicalizes absolute aliases; relative imports stay
    # bare — the short name catches the primitive either way
    src = """
    from hashlib import sha256 as s256
    from ..crypto.hashes import sha256

    def double(data):
        return s256(sha256(data)).digest()
    """
    fs = run(src, "hash-chokepoint", rel="tendermint_tpu/types/block.py")
    assert len(fs) == 2


def test_hub_routes_and_crypto_sink_are_clean():
    # the blessed funnel calls are exactly what the rule pushes toward
    src = """
    from ..crypto.hash_hub import sha256_many, sha256_one
    from ..crypto import merkle

    def roots(chunks, tx):
        return merkle.hash_from_byte_slices(chunks), sha256_one(tx)
    """
    assert run(src, "hash-chokepoint", rel="tendermint_tpu/types/block.py") == []
    # crypto/ is the sink: out of scope by construction, no pragma needed
    raw = """
    import hashlib
    def digest(m):
        return hashlib.sha256(m).digest()
    """
    assert run(raw, "hash-chokepoint", rel="tendermint_tpu/crypto/hashes.py") == []
    # and non-hot trees (tools/, rpc/) are out of scope too
    assert run(raw, "hash-chokepoint", rel="tendermint_tpu/tools/dumper.py") == []


def test_hash_chokepoint_pragma_needs_reason():
    flagged = """
    import hashlib
    def seed(label):
        return hashlib.sha256(label).digest()  # tmtlint: allow[hash-chokepoint]
    """
    fs = lint_source(
        textwrap.dedent(flagged),
        "tendermint_tpu/consensus/chaos.py",
        [RULES_BY_ID["hash-chokepoint"]],
        known_rules=set(RULES_BY_ID),
    )
    assert {f.rule for f in fs} == {"hash-chokepoint", BAD_PRAGMA}
    reasoned = """
    import hashlib
    def seed(label):
        return hashlib.sha256(label).digest()  # tmtlint: allow[hash-chokepoint] -- fixture: derivation, not a hot path
    """
    assert run(reasoned, "hash-chokepoint", rel="tendermint_tpu/consensus/chaos.py") == []


def test_hash_chokepoint_checked_in_allowlist():
    # the seeded chaos/attack harnesses are exempted by prefix in
    # allowlist.json — with the reason recorded there, not inline
    src = """
    import hashlib
    def derive(label):
        return hashlib.sha256(label).digest()
    """
    assert (
        run(
            src,
            "hash-chokepoint",
            rel="tendermint_tpu/consensus/byzantine.py",
            allowlist=Allowlist.load(DEFAULT_ALLOWLIST),
        )
        == []
    )
    # the exemption is prefix-scoped: a neighbor file is still flagged
    assert (
        len(
            run(
                src,
                "hash-chokepoint",
                rel="tendermint_tpu/consensus/state.py",
                allowlist=Allowlist.load(DEFAULT_ALLOWLIST),
            )
        )
        == 1
    )


# ---------------------------------------------------------------------------
# unbounded-queue


def test_unbounded_queue_flagged_on_flood_path():
    """Every queue on the tx-ingress / event-fan-out path buffers work
    an attacker generates for free — maxsize (plus shed-on-full) is
    mandatory there."""
    src = """
    import asyncio
    class Ingress:
        def __init__(self):
            self.q = asyncio.Queue()
            self.q0 = asyncio.Queue(0)
            self.qkw = asyncio.Queue(maxsize=0)
            self.qneg = asyncio.Queue(-1)  # asyncio: <= 0 means infinite
            self.qnegkw = asyncio.Queue(maxsize=-5)
    """
    for rel in (
        "tendermint_tpu/mempool/ingress.py",
        "tendermint_tpu/rpc/server.py",
        "tendermint_tpu/libs/pubsub.py",
    ):
        assert {f.line for f in run(src, "unbounded-queue", rel=rel)} == {
            5, 6, 7, 8, 9,
        }


def test_bounded_queue_and_out_of_scope_clean():
    bounded = """
    import asyncio
    class Ingress:
        def __init__(self, depth):
            self.q = asyncio.Queue(depth)
            self.q2 = asyncio.Queue(maxsize=depth + 1)
    """
    assert run(bounded, "unbounded-queue", rel="tendermint_tpu/mempool/ingress.py") == []
    # consensus internals are bounded by protocol structure, not by this
    # rule — the scope is the user-facing flood path only
    unbounded = """
    import asyncio
    q = asyncio.Queue()
    """
    assert run(unbounded, "unbounded-queue", rel="tendermint_tpu/consensus/state.py") == []


def test_unbounded_queue_from_import_cannot_evade():
    src = """
    from asyncio import Queue
    class Sub:
        def __init__(self):
            self.q = Queue()
    """
    assert len(run(src, "unbounded-queue", rel="tendermint_tpu/rpc/core.py")) == 1


def test_crypto_backends_allowlisted():
    src = """
    def check(pk, msg, sig):
        return pk.verify_signature(msg, sig)
    """
    allow = Allowlist.load(DEFAULT_ALLOWLIST)
    assert (
        run(src, "verify-chokepoint", rel="tendermint_tpu/crypto/batch.py", allowlist=allow)
        == []
    )
    # ...and the allowlist is per-rule, not a blanket file exemption
    assert (
        run(src, "verify-chokepoint", rel="tendermint_tpu/types/vote.py", allowlist=allow)
        != []
    )


# ---------------------------------------------------------------------------
# shape-bucketing


def test_prep_without_pad_to_flagged():
    """An unpadded kernel host-prep call hands XLA the raw batch length
    as a static shape — a cold compile per distinct size on the hot
    path. Both name-style and method-style calls are caught."""
    src = """
    from tendermint_tpu.crypto.tpu.verify import prepare_batch_eq

    def dispatch(tpuv, entries):
        a = prepare_batch_eq(entries)
        b = tpuv.prepare_resolved(entries)
        return a, b
    """
    fs = run(src, "shape-bucketing", rel="tendermint_tpu/crypto/tpu/somefile.py")
    assert [f.line for f in fs] == [5, 6]


def test_bls_pairing_prep_without_pad_to_flagged():
    """The BLS pairing prep is shape-gated like the ed25519 preps: an
    unpadded call cold-compiles a pairing kernel per batch length."""
    src = """
    def dispatch(items):
        return prepare_pairing_batch(items, pair_pad=2)
    """
    fs = run(src, "shape-bucketing", rel="tendermint_tpu/crypto/tpu/bls_x.py")
    assert len(fs) == 1 and "pad" in fs[0].message
    padded = """
    def dispatch(items, b):
        return prepare_pairing_batch(items, pad_to=b, pair_pad=2)
    """
    assert run(padded, "shape-bucketing", rel="tendermint_tpu/crypto/tpu/bls_x.py") == []


def test_prep_with_pad_to_clean():
    src = """
    def dispatch(tpuv, entries, b):
        ok = tpuv.prepare_batch_eq(entries, pad_to=b)
        ok2 = tpuv.prepare_batch(entries, pad_to=b)
        other = tpuv.prepare_dinner(entries)  # unrelated name
        return ok, ok2, other
    """
    assert run(src, "shape-bucketing", rel=NODE_PATH) == []


def test_prep_rule_relaxed_for_tests_profile():
    """tests/ build ad-hoc shapes on purpose (compile cost is theirs to
    pay); the rule only gates node code."""
    src = """
    def helper(tpuv, entries):
        return tpuv.prepare_batch_eq(entries)
    """
    assert run(src, "shape-bucketing", rel="tests/test_something.py") == []


# ---------------------------------------------------------------------------
# fs-discipline


def test_raw_binary_write_open_flagged():
    src = """
    def append(path, rec):
        with open(path, "ab") as f:
            f.write(rec)
    """
    fs = run(src, "fs-discipline", rel="tendermint_tpu/consensus/wal.py")
    assert len(fs) == 1


def test_read_only_and_fs_layer_opens_clean():
    src = """
    def read(self, path):
        with open(path, "rb") as f:
            return f.read()
    def append(self, path, rec):
        with self.fs.open(path, "ab") as f:
            f.write(rec)
    """
    assert run(src, "fs-discipline", rel="tendermint_tpu/consensus/wal.py") == []


def test_os_mutations_flagged_in_store_scope_only():
    src = """
    import os
    def swap(a, b):
        os.replace(a, b)
        os.fsync(3)
    """
    assert {f.line for f in run(src, "fs-discipline", rel="tendermint_tpu/store/x.py")} == {4, 5}
    # out of scope: p2p has no storage write path to protect
    assert run(src, "fs-discipline", rel="tendermint_tpu/p2p/x.py") == []


def test_sqlite_owned_db_allowlisted():
    src = """
    import os
    def swap(a, b):
        os.replace(a, b)
    """
    allow = Allowlist.load(DEFAULT_ALLOWLIST)
    assert (
        run(src, "fs-discipline", rel="tendermint_tpu/store/db.py", allowlist=allow)
        == []
    )


# ---------------------------------------------------------------------------
# nondeterminism


def test_global_random_flagged_seeded_instance_clean():
    bad = """
    import random
    def pick(peers):
        return random.choice(peers)
    """
    good = """
    import random
    def make_rng(seed):
        return random.Random(seed)
    def pick(rng, peers):
        return rng.choice(peers)
    """
    assert len(run(bad, "nondeterminism", rel="tendermint_tpu/p2p/pex.py")) == 1
    assert run(good, "nondeterminism", rel="tendermint_tpu/p2p/pex.py") == []


def test_os_entropy_flagged():
    src = """
    import os
    def nonce():
        return os.urandom(8)
    """
    assert len(run(src, "nondeterminism", rel="tendermint_tpu/libs/chaos.py")) == 1


def test_crypto_handshake_entropy_allowlisted():
    src = """
    import os
    def nonce():
        return os.urandom(8)
    """
    allow = Allowlist.load(DEFAULT_ALLOWLIST)
    assert (
        run(src, "nondeterminism", rel="tendermint_tpu/p2p/secret.py", allowlist=allow)
        == []
    )


def test_set_iteration_flagged_sorted_clean():
    bad = """
    def fanout(self, peers):
        for p in set(peers):
            self.send(p)
    """
    good = """
    def fanout(self, peers):
        for p in sorted(set(peers)):
            self.send(p)
    """
    assert len(run(bad, "nondeterminism", rel="tendermint_tpu/p2p/x.py")) == 1
    assert run(good, "nondeterminism", rel="tendermint_tpu/p2p/x.py") == []


# ---------------------------------------------------------------------------
# span-discipline


def test_span_outside_with_flagged():
    # held in a variable (never closed) and dropped on the floor — both
    # leak the measurement
    src = """
    from tendermint_tpu.libs import trace
    def f():
        sp = trace.span("hub", "dispatch")
        trace.span("hub", "queue")
    """
    assert {f.line for f in run(src, "span-discipline")} == {4, 5}


def test_span_in_with_clean():
    src = """
    from tendermint_tpu.libs import trace
    def f():
        with trace.span("hub", "dispatch") as sp:
            sp.set(batch=4)
        with trace.RECORDER.span("hub", "queue"):
            pass
    """
    assert run(src, "span-discipline") == []


def test_span_discipline_record_emit_exempt():
    # explicit-boundary APIs are closed by construction
    src = """
    from tendermint_tpu.libs import trace
    def f(ctx, t0, t1):
        trace.record(ctx, "consensus", "ingest.wait", t0, t1)
        trace.emit("backend", "attach", duration_s=0.5)
        trace.finish(ctx, "consensus", "msg")
    """
    assert run(src, "span-discipline") == []


def test_recorder_span_outside_with_flagged():
    src = """
    def f(recorder):
        leaked = recorder.span("hub", "x")
    """
    assert len(run(src, "span-discipline")) == 1


def test_unrelated_span_method_clean():
    # a .span() on a non-recorder receiver is not a trace span
    src = """
    def f(wing):
        area = wing.span("m")
    """
    assert run(src, "span-discipline") == []


def test_wall_clock_in_trace_layer_flagged():
    src = """
    import time
    def stamp():
        return time.time()
    """
    fs = run(src, "span-discipline", rel="tendermint_tpu/libs/trace.py")
    assert len(fs) == 1 and "wall-clock" in fs[0].message
    # time.monotonic is the duration domain — legal in the trace layer
    src_ok = """
    import time
    def dur():
        return time.monotonic()
    """
    assert run(src_ok, "span-discipline", rel="tendermint_tpu/libs/trace.py") == []
    # and wall clocks OUTSIDE the trace layer are other rules' business
    assert run(src, "span-discipline", rel="tendermint_tpu/rpc/core.py") == []


def test_watchdog_wall_clock_allowlisted():
    src = """
    import time
    def report_name():
        return f"wedged-{int(time.time()*1000)}.txt"
    """
    allow = Allowlist.load(DEFAULT_ALLOWLIST)
    assert (
        lint_source(
            textwrap.dedent(src),
            "tendermint_tpu/libs/watchdog.py",
            [RULES_BY_ID["span-discipline"]],
            allow,
        )
        == []
    )


# ---------------------------------------------------------------------------
# span-per-item


@pytest.mark.parametrize("call", [
    'with trace.span("tpu", "resolve"):\n                pass',
    'trace.record(ctx, "hub", "queue", 0.0, 1.0)',
    'trace.emit("hub", "cache_hit")',
    'trace.RECORDER.emit("hub", "cache_hit")',
])
def test_span_per_item_row_inside_a_loop_flagged(call):
    src = f"""
    from tendermint_tpu.libs import trace
    def f(items, ctx):
        for it in items:
            {call}
    """
    for rel in ("tendermint_tpu/crypto/batch.py", "tendermint_tpu/types/validation.py"):
        assert len(run(src, "span-per-item", rel=rel)) == 1, rel
    # outside the verify funnel a row per iteration is not this rule's business
    assert run(src, "span-per-item", rel="tendermint_tpu/blocksync/reactor.py") == []


def test_span_per_item_around_the_loop_clean():
    src = """
    from tendermint_tpu.libs import trace
    def f(items, target):
        with trace.span("tpu", "resolve", n=len(items)):
            for it in items:
                target.add(*it)
        return [trace.current() for _ in items]
    """
    assert run(src, "span-per-item", rel="tendermint_tpu/crypto/batch.py") == []


def test_span_per_item_comprehension_and_while_flagged_nested_def_not():
    src = """
    from tendermint_tpu.libs import trace
    def f(items):
        out = [trace.emit("a", "b") for _ in items]
        while items:
            trace.emit("a", "c")
            items.pop()
        for it in items:
            def later():
                trace.emit("a", "d")
    """
    assert {f.line for f in run(src, "span-per-item", rel="tendermint_tpu/crypto/x.py")} == {4, 6}


def test_span_per_item_pragma_names_the_bound():
    src = """
    from tendermint_tpu.libs import trace
    def f(chunks):
        for chunk in chunks:
            # tmtlint: allow[span-per-item] -- per chunk of 8192 signatures
            with trace.span("tpu", "prep", n=len(chunk)):
                pass
    """
    assert run(src, "span-per-item", rel="tendermint_tpu/crypto/tpu/verify.py") == []


# ---------------------------------------------------------------------------
# byz-containment


def test_byzantine_import_flagged_in_production_code():
    """The exact hazard the rule exists for: production wiring gaining
    a path to the unguarded double-signing strategy layer."""
    for src in (
        "from .consensus import byzantine",
        "from .consensus.byzantine import ByzConfig",
        "import tendermint_tpu.consensus.byzantine as byz",
    ):
        fs = run(src, "byz-containment", rel="tendermint_tpu/node.py")
        assert len(fs) == 1, src
        assert "quarantined" in fs[0].message
    # relative forms from inside the consensus package
    for src in (
        "from .byzantine import ByzantineNode",
        "from . import byzantine",
    ):
        fs = run(
            src, "byz-containment", rel="tendermint_tpu/consensus/routernet.py"
        )
        assert len(fs) == 1, src


def test_byzantine_import_allowed_in_harness_and_clean_elsewhere():
    # the scenario harness and the module itself ARE the legal users
    assert (
        run(
            "from .byzantine import ByzConfig, audit_net",
            "byz-containment",
            rel="tendermint_tpu/consensus/scenarios.py",
        )
        == []
    )
    assert (
        run(
            "from . import messages as m",
            "byz-containment",
            rel="tendermint_tpu/consensus/byzantine.py",
        )
        == []
    )
    # unrelated consensus imports never trip it
    assert (
        run(
            "from .consensus import messages, scenarios",
            "byz-containment",
            rel="tendermint_tpu/node.py",
        )
        == []
    )


def test_sync_facade_flagged_in_light():
    """LightFleet put light/ on the fleet-serving event loop: one
    blocking verify in a LightD coroutine stalls every concurrent sync
    session, so the sync facade (and direct verify) is a defect there."""
    src = """
    async def sync(self, height):
        ok = self.hub.verify_sync(pk, msg, sig)
        ok2 = self.hub.submit_nowait(pk, msg, sig).result(5.0)
    """
    fs = run(src, "verify-chokepoint", rel="tendermint_tpu/light/fleet.py")
    assert len(fs) == 2
    # sync defs in light/ stay legal (the stateless verifier core)
    clean = """
    def check(self, pk, msg, sig):
        return self.hub.verify_sync(pk, msg, sig)
    """
    assert run(clean, "verify-chokepoint", rel="tendermint_tpu/light/verifier.py") == []


def test_lunatic_provider_import_flagged_in_production_code():
    """light/byzantine (the lunatic forged-header provider) is
    quarantined exactly like consensus/byzantine: production wiring
    holding validator keys must never be able to sign a forged header."""
    for src, rel in (
        ("from .light import byzantine", "tendermint_tpu/node.py"),
        (
            "from .light.byzantine import LunaticProvider",
            "tendermint_tpu/node.py",
        ),
        (
            "import tendermint_tpu.light.byzantine as lb",
            "tendermint_tpu/cli.py",
        ),
        ("from .byzantine import LunaticConfig", "tendermint_tpu/light/fleet.py"),
        ("from . import byzantine", "tendermint_tpu/light/proxy.py"),
    ):
        fs = run(src, "byz-containment", rel=rel)
        assert len(fs) == 1, (src, rel)
        assert "quarantined" in fs[0].message


def test_lunatic_provider_import_allowed_in_harness_and_itself():
    # the scenario harness is the single legal injection seam for BOTH
    # quarantined strategy layers
    assert (
        run(
            "from ..light.byzantine import LunaticConfig, LunaticProvider",
            "byz-containment",
            rel="tendermint_tpu/consensus/scenarios.py",
        )
        == []
    )
    assert (
        run(
            "from .provider import Provider",
            "byz-containment",
            rel="tendermint_tpu/light/byzantine.py",
        )
        == []
    )
    # unrelated light imports never trip it
    assert (
        run(
            "from .light import fleet, verifier",
            "byz-containment",
            rel="tendermint_tpu/node.py",
        )
        == []
    )


def test_byzantine_containment_holds_on_the_real_tree():
    """The repo itself: the only files naming consensus/byzantine are
    the allowlisted harness modules (the whole-tree clean gate below
    covers this too — this pins the specific rule)."""
    from tendermint_tpu.tools.lint import lint_paths

    all_findings, n_files = lint_paths(
        [os.path.join(REPO, "tendermint_tpu")],
        [RULES_BY_ID["byz-containment"]],
        Allowlist.load(DEFAULT_ALLOWLIST),
    )
    findings = [f for f in all_findings if f.rule == "byz-containment"]
    assert n_files > 100  # the whole tree was actually scanned
    assert findings == [], [f.render() for f in findings]


def test_sync_facade_flagged_in_statesync():
    """BootFleet put statesync/ on the fleet-serving event loop: one
    blocking verify in a BootD coroutine stalls every concurrent chunk
    session AND every joiner's backfill batch, so the sync facade (and
    direct verify) is a defect there too."""
    src = """
    async def verify_backfill(self, blocks):
        ok = self.hub.verify_sync(pk, msg, sig)
        ok2 = self.hub.submit_nowait(pk, msg, sig).result(5.0)
    """
    fs = run(src, "verify-chokepoint", rel="tendermint_tpu/statesync/fleet.py")
    assert len(fs) == 2
    # sync defs in statesync/ stay legal (runs via asyncio.to_thread)
    clean = """
    def _check(self, pk, msg, sig):
        return self.hub.verify_sync(pk, msg, sig)
    """
    assert run(clean, "verify-chokepoint", rel="tendermint_tpu/statesync/fleet.py") == []


def test_poisoned_donor_import_flagged_in_production_code():
    """statesync/byzantine (the poisoned-snapshot donor app) is
    quarantined exactly like the other two strategy layers: a
    production node must be structurally unable to serve corrupted
    chunks to joiners."""
    for src, rel in (
        ("from .statesync import byzantine", "tendermint_tpu/node.py"),
        (
            "from .statesync.byzantine import PoisonedSnapshotApp",
            "tendermint_tpu/node.py",
        ),
        (
            "import tendermint_tpu.statesync.byzantine as sb",
            "tendermint_tpu/cli.py",
        ),
        ("from .byzantine import PoisonedSnapshotApp", "tendermint_tpu/statesync/fleet.py"),
        ("from . import byzantine", "tendermint_tpu/statesync/reactor.py"),
    ):
        fs = run(src, "byz-containment", rel=rel)
        assert len(fs) == 1, (src, rel)
        assert "quarantined" in fs[0].message
    # the scenario harness stays the single legal injection seam
    assert (
        run(
            "from ..statesync.byzantine import PoisonedSnapshotApp",
            "byz-containment",
            rel="tendermint_tpu/consensus/scenarios.py",
        )
        == []
    )
    # unrelated statesync imports never trip it
    assert (
        run(
            "from .statesync import fleet, reactor",
            "byz-containment",
            rel="tendermint_tpu/node.py",
        )
        == []
    )


# ---------------------------------------------------------------------------
# pragmas


PRAGMA_FIXTURE = """
import time
async def worker():
    time.sleep(1.0){pragma}
"""


def test_pragma_with_reason_suppresses():
    src = PRAGMA_FIXTURE.format(
        pragma="  # tmtlint: allow[blocking-in-async] -- fixture: startup only"
    )
    assert run_all(src) == []


def test_pragma_without_reason_does_not_suppress_and_is_reported():
    src = PRAGMA_FIXTURE.format(pragma="  # tmtlint: allow[blocking-in-async]")
    rules = {f.rule for f in run_all(src)}
    assert rules == {"blocking-in-async", BAD_PRAGMA}


def test_pragma_for_other_rule_does_not_suppress():
    src = PRAGMA_FIXTURE.format(
        pragma="  # tmtlint: allow[clock-discipline] -- wrong rule"
    )
    assert {f.rule for f in run_all(src)} == {"blocking-in-async"}


def test_wildcard_pragma_suppresses_everything():
    src = PRAGMA_FIXTURE.format(pragma="  # tmtlint: allow[*] -- fixture")
    assert run_all(src) == []


def test_comment_line_pragma_covers_next_code_line():
    src = """
    import time
    async def worker():
        # tmtlint: allow[blocking-in-async] -- fixture: covers the line below
        time.sleep(1.0)
    """
    assert run_all(src) == []


def test_stacked_comment_pragmas_all_cover_the_next_code_line():
    src = """
    import time, random
    async def worker():
        # tmtlint: allow[blocking-in-async] -- fixture: reason one
        # tmtlint: allow[nondeterminism] -- fixture: reason two
        time.sleep(random.random())
    """
    assert run_all(src, rel="tendermint_tpu/p2p/x.py") == []


def test_pragma_inside_string_literal_is_not_a_pragma():
    # pragma scanning is token-based: pragma-shaped TEXT in a string is
    # neither a suppression nor a bad-pragma (the line above in this
    # very file documents the syntax without tripping the tree gate)
    src = """
    import time
    async def worker():
        doc = "# tmtlint: allow[blocking-in-async] -- not a comment"
        time.sleep(1.0); bad = "# tmtlint: allow[blocking-in-async]"
    """
    assert {f.rule for f in run_all(src)} == {"blocking-in-async"}


def test_pragma_with_unknown_rule_id_is_reported():
    """A typo'd rule id used to suppress nothing and report nothing —
    the worst failure mode for an auditable-suppression scheme. With the
    registry handed to the run, the typo is itself a finding."""
    src = textwrap.dedent(
        """
        import time
        async def worker():
            time.sleep(1.0)  # tmtlint: allow[blocking-in-asink] -- typo'd id
        """
    )
    fs = lint_source(src, NODE_PATH, ALL_RULES, known_rules=set(RULES_BY_ID))
    rules = {f.rule for f in fs}
    # the typo'd pragma does not suppress, AND the typo is reported
    assert rules == {"blocking-in-async", BAD_PRAGMA}
    bad = [f for f in fs if f.rule == BAD_PRAGMA]
    assert any("unknown rule id" in f.message and "blocking-in-asink" in f.message
               for f in bad)


def test_pragma_with_known_ids_wildcard_and_badpragma_never_flagged_unknown():
    src = textwrap.dedent(
        """
        import time
        async def worker():
            time.sleep(1.0)  # tmtlint: allow[*] -- fixture
        """
    )
    assert lint_source(src, NODE_PATH, ALL_RULES, known_rules=set(RULES_BY_ID)) == []
    # without a registry (single-rule fixture runs) unknown ids are not
    # this run's business — same gating as bad-pragma vs --rule
    src2 = textwrap.dedent(
        """
        import time
        async def worker():
            time.sleep(1.0)  # tmtlint: allow[no-such-rule] -- still reported missing nothing
        """
    )
    fs = lint_source(src2, NODE_PATH, ALL_RULES)  # known_rules=None
    assert {f.rule for f in fs} == {"blocking-in-async"}


# ---------------------------------------------------------------------------
# driver + whole-tree gate (tier-1)


def _lint(*args: str) -> subprocess.CompletedProcess:
    """Run the REAL entrypoint (`scripts/tmtlint`) — the tier-1 gate,
    CI and pre-commit all go through this one file, so the gate test
    must too (one code path, no second driver to drift)."""
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tmtlint"), *args],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )


def test_repo_tree_is_clean_and_fast():
    """THE gate: the repo's own code holds every invariant the analyzers
    enforce — including the interprocedural and wire-schema passes —
    and the full run fits the tier-1 time budget (suite is ~815s of
    870s — this must stay a rounding error)."""
    out = _lint("--json")
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True
    assert payload["files_scanned"] > 100  # actually walked the tree
    assert len(payload["rules"]) >= 15
    # bench guard: wall time is recorded in the JSON and bounded
    assert payload["elapsed_s"] < 10.0, f"lint too slow: {payload['elapsed_s']}s"
    # per-rule finding counts ride the JSON (zeros included) so BENCH
    # rounds can diff lint drift across PRs
    assert set(payload["per_rule"]) == set(payload["rules"])
    assert all(v == 0 for v in payload["per_rule"].values())
    for required in (
        "transitive-blocking",
        "wire-schema",
        "wire-bounds",
        "wiregen-drift",
    ):
        assert required in payload["per_rule"]


def test_driver_rule_filter_and_errors():
    out = _lint("--rule", "no-such-rule")
    assert out.returncode == 2 and "unknown rule" in out.stderr
    out = _lint("--list-rules")
    assert out.returncode == 0
    for rule in ALL_RULES:
        assert rule.id in out.stdout


def test_driver_rejects_nonexistent_paths():
    # a typo'd path must NOT scan 0 files and report clean
    out = _lint("no/such/dir")
    assert out.returncode == 2 and "no such path" in out.stderr


def test_single_rule_run_reports_only_that_rule(tmp_path):
    # bad pragmas elsewhere in a file must not fail a --rule spot check
    # (they belong to the full gate); the shims rely on this
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)  # tmtlint: allow[task-leak]\n"
    )
    out = _lint("--rule", "task-leak", "--json", str(bad))
    assert out.returncode == 0, out.stdout + out.stderr
    assert json.loads(out.stdout)["findings"] == []
    # the full run still reports both the finding and the bad pragma
    out = _lint("--json", str(bad))
    rules = {f["rule"] for f in json.loads(out.stdout)["findings"]}
    assert rules == {"blocking-in-async", BAD_PRAGMA}


def test_driver_reports_findings_with_location(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import asyncio\n"
        "async def f(self):\n"
        "    asyncio.ensure_future(self.g())\n"
    )
    out = _lint(str(bad))
    assert out.returncode == 1
    assert "task-leak" in out.stderr and "bad.py:3" in out.stderr
    out = _lint("--json", str(bad))
    assert out.returncode == 1
    payload = json.loads(out.stdout)
    assert [f["rule"] for f in payload["findings"]] == ["task-leak"]
    assert payload["findings"][0]["line"] == 3
