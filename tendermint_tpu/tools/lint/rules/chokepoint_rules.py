"""Chokepoint analyzers — AST ports of the PR 2/PR 3 regex lints.

verify-chokepoint: every signature check routes through the VerifyHub
(`crypto/verify_hub.verify_one` / `verify_many` or the validation
`_CommitVerifier` shim) so it participates in micro-batching and
gossip-duplicate dedup. A new direct `*.verify_signature(...)` call
site silently bypasses batching — the paper's headline metric (commit
sigs verified/sec) regresses with no test failing.

shape-bucketing: every host-prep call that feeds a verify kernel
(`prepare_batch_eq` / `prepare_resolved` / `prepare_batch`) must pass
``pad_to=`` — an unpadded call hands XLA the raw batch length as a
static shape, and every new length is an inline cold compile on the hot
path (tens of seconds to minutes per shape on the device). The dispatch core additionally asserts the padded shape is a
bucket-ladder shape at runtime (crypto/tpu/verify._is_warm_bucket).

fs-discipline: storage-layer writes go through the injectable
`libs/chaosfs.FS`. The crash-consistency guarantees (torn-write /
lost-fsync / ENOSPC recovery, tests/test_crash_recovery.py) only hold
for I/O the chaos layer can see; a raw `open(path, "ab")` in the WAL
escapes both fault injection and the durable-watermark crash model.

The AST versions resolve actual call expressions, so `self.fs.open(...)`
(the discipline itself) is structurally distinguished from the builtin
`open(...)` instead of regex-guessed, and `def verify_signature`
interface definitions never need special-casing.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..framework import FileContext, Finding, Rule, call_name, method_name


class VerifyChokepoint(Rule):
    id = "verify-chokepoint"
    doc = (
        "no direct *.verify_signature() outside the crypto/handshake/"
        "harness allowlist — route through crypto/verify_hub; no "
        "sync-facade verification (verify_sync / submit_nowait().result())"
        " inside coroutines in consensus/blocksync/statesync; no "
        "direct BLS pairing/aggregate-verify calls outside crypto/ — "
        "route aggregate commits through verify_hub.verify_aggregate "
        "(the pairing modules must not grow a second verify funnel); "
        "and no direct verifyd socket-protocol calls outside crypto/ — "
        "crypto/verifyd is the ONLY legal raw-socket verify path (set "
        "[verify_hub] verifyd_sock and let the hub route)"
    )
    scope = ("tendermint_tpu/",)
    profiles = ("node",)

    #: the BLS pairing/verify primitives (crypto/bls_math, crypto/bls,
    #: crypto/tpu/bls_pairing, crypto/batch): calling one of these
    #: outside crypto/ bypasses the hub's aggregate verdict cache and
    #: the breaker-guarded device routing. PoP checks (pop_verify) are
    #: construction-time, not the verify hot path, and stay legal.
    BLS_FUNNEL_CALLS = frozenset(
        {
            "pairing",
            "multi_pairing",
            "miller_loop",
            "final_exp",
            "aggregate_verify",
            "bls_aggregate_verify",
            "verify_pairs_batch",
            "verify_items",
        }
    )

    #: the verifyd sidecar protocol surface (crypto/verifyd.py): a
    #: direct socket verify outside crypto/ bypasses the hub's verdict
    #: cache, lanes, AND the circuit-breaker fallback contract — a
    #: daemon crash at such a call site becomes a liveness event
    #: instead of an inline-local degrade. `remote_stats` stays legal
    #: (diagnostics, not a verify path).
    VERIFYD_FUNNEL_CALLS = frozenset(
        {
            "remote_verify_batch",
            "remote_verify_aggregate",
            "VerifydClient",
            "client_for",
        }
    )

    #: dirs where the pipelined ingest made the SYNC hub facade inside a
    #: coroutine a defect: it blocks the event loop on one signature and
    #: pins batch occupancy at 1 — use `await hub.verify(...)` (or hand
    #: the work to the ingest pipeline / asyncio.to_thread). mempool/
    #: and rpc/ joined with TxIngress: the tx-flood front door lives on
    #: the event loop and one sync verify stalls every admission.
    #: light/ joined with LightFleet: a LightD serves a whole client
    #: fleet from one event loop, and one blocking verify stalls every
    #: concurrent sync session behind a single signature
    ASYNC_SCOPES = (
        "tendermint_tpu/consensus/",
        "tendermint_tpu/blocksync/",
        "tendermint_tpu/statesync/",
        "tendermint_tpu/mempool/",
        "tendermint_tpu/rpc/",
        "tendermint_tpu/light/",
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        in_async_scope = any(ctx.rel.startswith(p) for p in self.ASYNC_SCOPES)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if method_name(node) == "verify_signature":
                yield ctx.finding(
                    self.id,
                    node,
                    "direct verify_signature() bypasses VerifyHub "
                    "micro-batching and verdict dedup (the commit-sigs/sec "
                    "north star); route through crypto/verify_hub.verify_one "
                    "or the validation batch shim",
                )
                continue
            name = method_name(node) or call_name(node)
            if (
                name is not None
                and name.rsplit(".", 1)[-1] in self.BLS_FUNNEL_CALLS
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"direct BLS `{name.rsplit('.', 1)[-1]}()` outside "
                    "crypto/ creates a second verify funnel — aggregate "
                    "commits route through crypto/verify_hub."
                    "verify_aggregate (verdict cache + breaker-guarded "
                    "device routing)",
                )
                continue
            if (
                name is not None
                and name.rsplit(".", 1)[-1] in self.VERIFYD_FUNNEL_CALLS
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"direct verifyd `{name.rsplit('.', 1)[-1]}()` outside "
                    "crypto/ — the sidecar protocol module is the only "
                    "legal raw-socket verify path; set [verify_hub] "
                    "verifyd_sock and route through the hub (verdict "
                    "cache, lanes, breaker-guarded inline-local fallback)",
                )
                continue
            if not (in_async_scope and ctx.in_async_def(node)):
                continue
            if method_name(node) == "verify_sync":
                yield ctx.finding(
                    self.id,
                    node,
                    "hub.verify_sync() inside a coroutine blocks the event "
                    "loop on ONE signature and pins batch occupancy at 1 — "
                    "await the async hub.verify() (the pipelined-ingest "
                    "path) instead",
                )
            elif method_name(node) == "result" and self._submit_receiver(node):
                yield ctx.finding(
                    self.id,
                    node,
                    "submit_nowait(...).result() inside a coroutine is the "
                    "sync facade in disguise (blocks the loop per "
                    "signature); await asyncio.wrap_future(...) or the "
                    "async hub.verify() instead",
                )

    @staticmethod
    def _submit_receiver(node: ast.Call) -> bool:
        """True for `<expr>.submit_nowait(...).result(...)` chains."""
        recv = node.func.value  # method_name() proved func is Attribute
        return (
            isinstance(recv, ast.Call)
            and method_name(recv) == "submit_nowait"
        )


class HashChokepoint(Rule):
    id = "hash-chokepoint"
    doc = (
        "no raw SHA-256 (`hashlib.sha256` / `crypto.hashes.sha256`) in "
        "hot paths outside crypto/ — route through crypto/hash_hub "
        "(`sha256_many` for batches, `sha256_one` for singles) so "
        "hashing rides the lane accounting, hashhub_* metrics, and the "
        "breaker-guarded device route; crypto/ stays the sink"
    )
    #: the hash hot paths: block/part/tx hashing (types/), app-hash and
    #: indexing (state/), the consensus loop, the tx front door
    #: (mempool/), and LightD hop serving (light/). crypto/ is the sink
    #: and is out of scope by construction.
    scope = (
        "tendermint_tpu/types/",
        "tendermint_tpu/state/",
        "tendermint_tpu/consensus/",
        "tendermint_tpu/mempool/",
        "tendermint_tpu/light/",
    )
    profiles = ("node",)

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            # resolve_call canonicalizes `import hashlib as h` /
            # `from hashlib import sha256 as s`; relative imports
            # (`from ..crypto.hashes import sha256`) stay bare, so the
            # short name is what identifies the primitive either way
            name = ctx.resolve_call(node)
            if name is None or name.rsplit(".", 1)[-1] != "sha256":
                continue
            yield ctx.finding(
                self.id,
                node,
                f"raw `{name}()` in a hash hot path bypasses the HashHub "
                "(lane accounting, hashhub_* metrics, breaker-guarded "
                "device batching); route through crypto/hash_hub."
                "sha256_many / sha256_one — or crypto/merkle for trees",
            )


class FsDiscipline(Rule):
    id = "fs-discipline"
    doc = (
        "WAL/store/state write paths must use the injectable "
        "libs/chaosfs.FS — no raw binary open() writes or os.* mutations"
    )
    scope = (
        "tendermint_tpu/consensus/wal.py",
        "tendermint_tpu/store/",
        "tendermint_tpu/state/",
    )
    profiles = ("node",)

    OS_MUTATIONS = {
        "os.write",
        "os.fsync",
        "os.open",
        "os.rename",
        "os.replace",
        "os.remove",
        "os.unlink",
        "os.truncate",
        "os.ftruncate",
    }

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.resolve_call(node)
            if name in self.OS_MUTATIONS:
                yield ctx.finding(
                    self.id,
                    node,
                    f"raw `{name}()` in a storage write path escapes chaos-fs "
                    "fault injection and the durable-watermark crash model; "
                    "use the injected libs/chaosfs.FS",
                )
            elif name == "open" and self._binary_write_mode(node):
                yield ctx.finding(
                    self.id,
                    node,
                    "raw binary-write `open()` in a storage path: the "
                    "crash-recovery matrix cannot inject faults it cannot "
                    "see; use fs.open(...) from the injected chaos-fs layer",
                )

    @staticmethod
    def _binary_write_mode(node: ast.Call) -> bool:
        mode = None
        if len(node.args) >= 2:
            mode = node.args[1]
        for kw in node.keywords:
            if kw.arg == "mode":
                mode = kw.value
        if not (isinstance(mode, ast.Constant) and isinstance(mode.value, str)):
            return False
        m = mode.value
        return "b" in m and any(c in m for c in "wax+")


class ShapeBucketing(Rule):
    id = "shape-bucketing"
    doc = (
        "kernel host-prep calls (prepare_batch_eq / prepare_resolved / "
        "prepare_batch) must pass pad_to= — a raw batch length is a "
        "cold XLA compile per distinct size on the hot path; route "
        "through pad-to-bucket or the CPU fallback"
    )
    scope = ("tendermint_tpu/",)
    profiles = ("node",)

    PREP_CALLS = (
        "prepare_batch_eq",
        "prepare_resolved",
        "prepare_batch",
        "prepare_pairing_batch",
    )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = method_name(node) or call_name(node)
            if name is None:
                continue
            short = name.rsplit(".", 1)[-1]
            if short not in self.PREP_CALLS:
                continue
            if any(kw.arg == "pad_to" for kw in node.keywords):
                continue
            yield ctx.finding(
                self.id,
                node,
                f"`{short}(...)` without pad_to= compiles a cold XLA "
                "shape per distinct batch length on the hot path; pad "
                "to a warmed bucket (crypto/tpu/verify._bucket) or take "
                "the CPU fallback",
            )


RULES = (VerifyChokepoint(), HashChokepoint(), FsDiscipline(), ShapeBucketing())
