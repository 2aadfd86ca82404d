"""tmtlint v2 — the tree-wide passes (ProjectContext, interprocedural
rules, wire-schema lockfile).

Fixture seam: `lint_tree({rel: source, ...})` builds a real
ProjectContext over an in-memory tree, so every test here sees exactly
what a full scan would — import resolution (absolute AND relative),
call-graph edges, chain-breaking pragmas, lockfile diffing.

The acceptance pins live here too: the 2-hop blocking fixture that the
per-file rule PROVABLY misses (asserted both ways), the renumbered
fixture copy of consensus/messages.py failing with old/new field
numbers in the message, and the real-tree lockfile completeness check.
"""

from __future__ import annotations

import copy
import json
import os
import subprocess
import sys
import textwrap

from tendermint_tpu.tools.lint import (
    ALL_RULES,
    DEFAULT_ALLOWLIST,
    RULES_BY_ID,
    Allowlist,
    FileContext,
    ProjectContext,
    lint_source,
    lint_tree,
)
from tendermint_tpu.tools.lint.framework import _parse_context
from tendermint_tpu.tools.lint.rules.wire_rules import (
    LOCKFILE,
    WireSchema,
    extract_wire_schema,
    file_uses_protoenc,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALLOW = Allowlist.load(DEFAULT_ALLOWLIST)


def dedent_tree(sources: dict[str, str]) -> dict[str, str]:
    return {rel: textwrap.dedent(src) for rel, src in sources.items()}


def run_tree(sources: dict[str, str], rule_id: str | None = None, **kw):
    out = lint_tree(dedent_tree(sources), ALL_RULES, ALLOW, **kw)
    if rule_id is not None:
        out = [f for f in out if f.rule == rule_id]
    return out


def make_pctx(sources: dict[str, str], full_tree: bool = True) -> ProjectContext:
    files = {}
    for rel, src in dedent_tree(sources).items():
        ctx = _parse_context(src, rel)
        assert isinstance(ctx, FileContext), f"fixture does not parse: {rel}"
        files[rel] = ctx
    pctx = ProjectContext(files, full_tree=full_tree)
    pctx.allowlist = ALLOW
    return pctx


# ---------------------------------------------------------------------------
# transitive-blocking — THE acceptance fixture


TWO_HOP = {
    "tendermint_tpu/consensus/somefile.py": """
    from ..libs import helpers

    async def handle_vote(self, vote):
        helpers.normalize(vote)
        return vote
    """,
    "tendermint_tpu/libs/helpers.py": """
    import time

    def normalize(vote):
        _settle(vote)
        return vote

    def _settle(vote):
        time.sleep(0.5)
    """,
}


def test_two_hop_blocking_chain_missed_by_per_file_rule():
    """The acceptance pin, both directions: the per-file rule passes
    this fixture (each file alone holds its invariant — no blocking
    call is lexically inside an async def), the project rule fails it
    at the coroutine with the whole chain in the message."""
    # old rule, file by file: provably clean
    for rel, src in dedent_tree(TWO_HOP).items():
        per_file = lint_source(
            src, rel, [RULES_BY_ID["blocking-in-async"]], ALLOW
        )
        assert per_file == [], (rel, [f.render() for f in per_file])
    # new pass: one finding, at the coroutine's call line
    fs = run_tree(TWO_HOP, "transitive-blocking")
    assert len(fs) == 1
    f = fs[0]
    assert f.path == "tendermint_tpu/consensus/somefile.py"
    assert f.line == 5  # the helpers.normalize(vote) call
    assert "handle_vote" in f.message
    assert "time.sleep" in f.message
    # the chain names BOTH hops with their files
    assert "normalize" in f.message and "_settle" in f.message
    assert "tendermint_tpu/libs/helpers.py" in f.message
    assert "2 hop(s)" in f.message


def test_intermediate_pragma_breaks_the_chain():
    """A reasoned pragma on the PRIMITIVE line (the audited boundary)
    suppresses the chain for every caller above it."""
    fixed = copy.deepcopy(TWO_HOP)
    fixed["tendermint_tpu/libs/helpers.py"] = """
    import time

    def normalize(vote):
        _settle(vote)
        return vote

    def _settle(vote):
        time.sleep(0.5)  # tmtlint: allow[blocking-in-async] -- fixture: measured sub-ms stub
    """
    assert run_tree(fixed, "transitive-blocking") == []
    # ... and a pragma on the intermediate EDGE works the same
    fixed["tendermint_tpu/libs/helpers.py"] = """
    import time

    def normalize(vote):
        _settle(vote)  # tmtlint: allow[transitive-blocking] -- fixture: cold path only
        return vote

    def _settle(vote):
        time.sleep(0.5)
    """
    assert run_tree(fixed, "transitive-blocking") == []


def test_pragma_at_the_coroutine_call_site_suppresses():
    fixed = copy.deepcopy(TWO_HOP)
    fixed["tendermint_tpu/consensus/somefile.py"] = """
    from ..libs import helpers

    async def handle_vote(self, vote):
        helpers.normalize(vote)  # tmtlint: allow[transitive-blocking] -- fixture: startup only
        return vote
    """
    assert run_tree(fixed, "transitive-blocking") == []


def test_three_hop_chain_and_self_method_resolution():
    """Chains propagate through `self.` method calls and `from x import
    f` bindings alike."""
    tree = {
        "tendermint_tpu/consensus/deep.py": """
        from ..libs.helpers import normalize

        class Reactor:
            async def on_frame(self, frame):
                self._apply(frame)

            def _apply(self, frame):
                normalize(frame)
        """,
        "tendermint_tpu/libs/helpers.py": """
        import subprocess

        def normalize(frame):
            _shell(frame)

        def _shell(frame):
            subprocess.run(["true"])
        """,
    }
    fs = run_tree(tree, "transitive-blocking")
    assert len(fs) == 1
    assert fs[0].line == 6  # the self._apply call inside the coroutine
    assert "subprocess.run" in fs[0].message
    assert "_apply" in fs[0].message and "_shell" in fs[0].message


def test_async_callees_and_to_thread_do_not_propagate():
    tree = {
        "tendermint_tpu/consensus/ok.py": """
        import asyncio
        from ..libs import helpers

        async def fine(self):
            await helpers.awaitable()          # async callee: not a sync chain
            await asyncio.to_thread(helpers.heavy)  # the FIX, not a finding
        """,
        "tendermint_tpu/libs/helpers.py": """
        import time, asyncio

        async def awaitable():
            await asyncio.sleep(0)

        def heavy():
            time.sleep(1.0)
        """,
    }
    assert run_tree(tree, "transitive-blocking") == []


def test_tests_profile_coroutines_exempt():
    tree = {
        "tests/test_x.py": """
        from tendermint_tpu.libs import helpers

        async def helper():
            helpers.normalize(1)
        """,
        "tendermint_tpu/libs/helpers.py": """
        import time

        def normalize(x):
            time.sleep(0.1)
        """,
    }
    assert run_tree(tree, "transitive-blocking") == []


def test_cycle_in_call_graph_terminates():
    tree = {
        "tendermint_tpu/consensus/cyc.py": """
        async def outer(self):
            a()

        def a():
            b()

        def b():
            a()
        """,
    }
    assert run_tree(tree, "transitive-blocking") == []


def test_cycle_truncated_search_does_not_poison_the_memo():
    """Review-pass regression: exploring x while y is on the DFS stack
    prunes x->y as a cycle; that TRUNCATED negative must not be cached,
    or a later query entering at x (whose real witness runs x->y->z->
    sleep) silently comes back clean — a false negative in every chain
    rule. Both coroutines must be flagged."""
    tree = {
        "tendermint_tpu/consensus/cycmemo.py": """
        import time

        async def c1(self):
            y()

        async def c2(self):
            x()

        def y():
            x()
            z()

        def x():
            y()

        def z():
            time.sleep(1)
        """,
    }
    fs = run_tree(tree, "transitive-blocking")
    assert len(fs) == 2, [f.render() for f in fs]
    assert {f.line for f in fs} == {5, 8}  # both coroutines' call sites
    assert all("time.sleep" in f.message for f in fs)


def test_restrict_to_filters_per_file_but_never_project_findings(tmp_path):
    """Review-pass regression (--changed contract): editing ONLY the
    helper must still surface the transitive finding that lands at the
    untouched coroutine, while per-file findings in untouched files
    stay filtered (pre-existing debt is the full gate's business)."""
    from tendermint_tpu.tools.lint import lint_paths

    repo = tmp_path
    (repo / "tendermint_tpu" / "consensus").mkdir(parents=True)
    (repo / "tendermint_tpu" / "libs").mkdir(parents=True)
    (repo / "tendermint_tpu" / "consensus" / "x.py").write_text(
        textwrap.dedent(
            """
            import time
            from ..libs.h import helper

            async def on_msg(self):
                helper()

            async def untouched_direct(self):
                time.sleep(1)  # per-file finding in an UNCHANGED file
            """
        )
    )
    (repo / "tendermint_tpu" / "libs" / "h.py").write_text(
        textwrap.dedent(
            """
            import time

            def helper():
                time.sleep(1)
            """
        )
    )
    rules = [RULES_BY_ID["blocking-in-async"], RULES_BY_ID["transitive-blocking"]]
    # pretend only the helper changed
    findings, n = lint_paths(
        ["tendermint_tpu"],
        rules,
        ALLOW,
        repo=str(repo),
        report_pragma_errors=False,
        restrict_to=["tendermint_tpu/libs/h.py"],
    )
    assert n == 2
    by_rule = {f.rule for f in findings}
    # the cross-file consequence IS reported, at the untouched coroutine
    assert "transitive-blocking" in by_rule
    assert any(
        f.rule == "transitive-blocking"
        and f.path == "tendermint_tpu/consensus/x.py"
        for f in findings
    )
    # the unrelated per-file finding in the untouched file is filtered
    assert "blocking-in-async" not in by_rule
    # ... and unfiltered without the restriction
    findings_full, _ = lint_paths(
        ["tendermint_tpu"], rules, ALLOW, repo=str(repo),
        report_pragma_errors=False,
    )
    assert any(f.rule == "blocking-in-async" for f in findings_full)


# ---------------------------------------------------------------------------
# transitive-verify


def test_coroutine_reaching_sync_facade_through_helper_flagged():
    """The helper's verify_sync is legal standing alone (sync contexts
    may block) — the call FROM a consensus coroutine is the defect, and
    only the call graph sees it."""
    tree = {
        "tendermint_tpu/consensus/ingest2.py": """
        from ..types.validation import check_commit

        async def on_commit(self, commit):
            check_commit(self.hub, commit)
        """,
        "tendermint_tpu/types/validation.py": """
        def check_commit(hub, commit):
            return hub.verify_sync(commit.pk, commit.msg, commit.sig)
        """,
    }
    # per-file: clean (validation.py is sync, outside ASYNC_SCOPES)
    for rel, src in dedent_tree(tree).items():
        assert lint_source(src, rel, [RULES_BY_ID["verify-chokepoint"]], ALLOW) == []
    fs = run_tree(tree, "transitive-verify")
    assert len(fs) == 1
    assert fs[0].path == "tendermint_tpu/consensus/ingest2.py"
    assert "verify_sync" in fs[0].message and "check_commit" in fs[0].message


def test_chain_into_crypto_is_a_legal_sink():
    """crypto/ IS the chokepoint: a chain that enters an allowlisted
    file stops — calling the hub's own machinery is the blessed path,
    not a bypass."""
    tree = {
        "tendermint_tpu/consensus/ingest3.py": """
        from ..crypto.verify_hub import hub_helper

        async def on_commit(self, commit):
            hub_helper(commit)
        """,
        "tendermint_tpu/crypto/verify_hub.py": """
        def hub_helper(commit):
            return commit.pk.verify_signature(commit.msg, commit.sig)
        """,
    }
    assert run_tree(tree, "transitive-verify") == []


def test_verify_signature_through_helper_flagged_outside_async_scope_helpers():
    tree = {
        "tendermint_tpu/blocksync/pool2.py": """
        from ..types.util import raw_check

        async def verify_block(self, b):
            raw_check(b)
        """,
        "tendermint_tpu/types/util.py": """
        def raw_check(b):
            return b.pk.verify_signature(b.msg, b.sig)
        """,
    }
    fs = run_tree(tree, "transitive-verify")
    assert len(fs) == 1 and "verify_signature" in fs[0].message


# ---------------------------------------------------------------------------
# transitive-fs


def test_storage_path_reaching_raw_write_through_libs_helper_flagged():
    tree = {
        "tendermint_tpu/consensus/wal.py": """
        from ..libs.diskutil import atomic_write

        class WAL:
            def flush(self, path, data):
                atomic_write(path, data)
        """,
        "tendermint_tpu/libs/diskutil.py": """
        import os

        def atomic_write(path, data):
            with open(path + ".tmp", "wb") as f:
                f.write(data)
            os.replace(path + ".tmp", path)
        """,
    }
    # per-file: clean — libs/ is outside the fs-discipline scope and
    # wal.py itself holds no raw write
    for rel, src in dedent_tree(tree).items():
        assert lint_source(src, rel, [RULES_BY_ID["fs-discipline"]], ALLOW) == []
    fs = run_tree(tree, "transitive-fs")
    assert len(fs) == 1
    assert fs[0].path == "tendermint_tpu/consensus/wal.py"
    assert "atomic_write" in fs[0].message
    assert "chaos" in fs[0].message


def test_fs_chain_into_allowlisted_db_is_legal():
    tree = {
        "tendermint_tpu/store/blockstore2.py": """
        from .db import persist

        class Store:
            def save(self, k, v):
                persist(k, v)
        """,
        "tendermint_tpu/store/db.py": """
        import os

        def persist(k, v):
            os.replace(k, v)
        """,
    }
    assert run_tree(tree, "transitive-fs") == []


# ---------------------------------------------------------------------------
# transitive-cleanup


def test_cleanup_await_reaching_unshielded_wait_for_flagged():
    tree = {
        "tendermint_tpu/libs/svc2.py": """
        import asyncio

        class Svc:
            async def stop(self):
                try:
                    await self.run()
                finally:
                    await self._drain()

            async def _drain(self):
                await asyncio.wait_for(self._flush(), 1.0)

            async def _flush(self):
                pass
        """,
    }
    # per-file absorbed-cancellation: clean — the wait_for is NOT
    # lexically in a cleanup context
    src = dedent_tree(tree)["tendermint_tpu/libs/svc2.py"]
    assert (
        lint_source(src, "tendermint_tpu/libs/svc2.py",
                    [RULES_BY_ID["absorbed-cancellation"]], ALLOW)
        == []
    )
    fs = run_tree(tree, "transitive-cleanup")
    assert len(fs) == 1
    assert "_drain" in fs[0].message and "wait_for" in fs[0].message


def test_shielded_wait_for_in_helper_clean():
    tree = {
        "tendermint_tpu/libs/svc3.py": """
        import asyncio

        class Svc:
            async def stop(self):
                try:
                    await self.run()
                finally:
                    await self._drain()

            async def _drain(self):
                await asyncio.wait_for(asyncio.shield(self._flush()), 1.0)

            async def _flush(self):
                pass
        """,
    }
    assert run_tree(tree, "transitive-cleanup") == []


# ---------------------------------------------------------------------------
# wire-bounds (per-file — fixtures ride lint_source like the others)


WIRE_BOUNDS_POS = """
from ..libs import protoenc as pe

def decode_things(data):
    r = pe.Reader(data)
    out = []
    while not r.eof():
        f, wt = r.read_tag()
        if f == 1:
            out.append(r.read_bytes())
        else:
            r.skip(wt)
    return out
"""


def test_unbounded_decode_growth_flagged():
    fs = lint_source(
        textwrap.dedent(WIRE_BOUNDS_POS),
        "tendermint_tpu/types/somewire.py",
        [RULES_BY_ID["wire-bounds"]],
        ALLOW,
    )
    assert len(fs) == 1 and "MAX_" in fs[0].message


def test_bounded_decode_growth_clean():
    src = """
    from ..libs import protoenc as pe

    MAX_THINGS = 1024

    def decode_things(data):
        r = pe.Reader(data)
        out = []
        while not r.eof():
            f, wt = r.read_tag()
            if f == 1:
                out.append(r.read_bytes())
                if len(out) > MAX_THINGS:
                    raise ValueError("too many things")
            else:
                r.skip(wt)
        return out
    """
    assert (
        lint_source(textwrap.dedent(src), "tendermint_tpu/types/somewire.py",
                    [RULES_BY_ID["wire-bounds"]], ALLOW)
        == []
    )


def test_decoded_count_range_flagged_and_checker_call_counts_as_clamp():
    bad = """
    from ..libs import protoenc as pe

    def decode_n(data):
        r = pe.Reader(data)
        out = []
        while not r.eof():
            f, wt = r.read_tag()
            for _ in range(r.read_uvarint()):
                out.append(f)
        return out
    """
    fs = lint_source(textwrap.dedent(bad), "tendermint_tpu/types/w2.py",
                     [RULES_BY_ID["wire-bounds"]], ALLOW)
    assert any("range" in f.message for f in fs)
    good = """
    from ..libs import protoenc as pe

    MAX_N = 64

    def _chk(lst, bound, what):
        if len(lst) > bound:
            raise ValueError(what)

    def decode_things(data):
        r = pe.Reader(data)
        out = []
        while not r.eof():
            f, wt = r.read_tag()
            if f == 1:
                out.append(r.read_bytes())
                _chk(out, MAX_N, "things")
            else:
                r.skip(wt)
        return out
    """
    assert (
        lint_source(textwrap.dedent(good), "tendermint_tpu/types/w2.py",
                    [RULES_BY_ID["wire-bounds"]], ALLOW)
        == []
    )


def test_wire_bounds_relaxed_for_tests_profile():
    assert (
        lint_source(textwrap.dedent(WIRE_BOUNDS_POS), "tests/test_w.py",
                    [RULES_BY_ID["wire-bounds"]], ALLOW)
        == []
    )


# ---------------------------------------------------------------------------
# wire-schema — lockfile mutation matrix


WIRE_TREE = {
    "tendermint_tpu/proto1/messages.py": """
    from ..libs import protoenc as pe

    T_PING = 1
    T_PONG = 2
    MAX_ITEMS = 64
    PROTO1_CHANNEL = 0x70

    def encode_ping(seq, payload):
        body = pe.varint_field(1, seq) + pe.bytes_field(2, payload)
        return pe.message_field(T_PING, body)

    def decode_frame(data):
        r = pe.Reader(data)
        f, wt = r.read_tag()
        body = r.read_bytes()
        items = []
        if f == T_PING:
            br = pe.Reader(body)
            while not br.eof():
                bf, bwt = br.read_tag()
                if bf == 1:
                    seq = br.read_uvarint()
                elif bf == 2:
                    items.append(br.read_bytes())
                    if len(items) > MAX_ITEMS:
                        raise ValueError("too many")
                else:
                    br.skip(bwt)
        return items
    """,
}


def wire_lock(tree: dict[str, str]) -> dict:
    return extract_wire_schema(make_pctx(tree))


def run_wire(tree: dict[str, str], lock: dict, full_tree: bool = True):
    rules = [r for r in ALL_RULES if r.id != "wire-schema"]
    rules.append(WireSchema(lock=lock))
    fs = lint_tree(dedent_tree(tree), rules, ALLOW, full_tree=full_tree)
    return [f for f in fs if f.rule == "wire-schema"]


def test_update_lock_round_trips_clean():
    lock = wire_lock(WIRE_TREE)
    assert run_wire(WIRE_TREE, lock) == []


def test_renumbered_field_fails_with_old_and_new_numbers():
    lock = wire_lock(WIRE_TREE)
    mutated = {
        "tendermint_tpu/proto1/messages.py": WIRE_TREE[
            "tendermint_tpu/proto1/messages.py"
        ].replace("pe.varint_field(1, seq)", "pe.varint_field(6, seq)")
    }
    fs = run_wire(mutated, lock)
    assert len(fs) == 1
    # old AND new numbers in the message — the reviewable diff
    assert "1:varint" in fs[0].message and "6:varint" in fs[0].message
    assert "encode_ping" in fs[0].message


FOLDED_PING = """
    from ..libs import protoenc as pe

    T_PING = 1
    MAX_ITEMS = 64
    PROTO1_CHANNEL = 0x70
    _SEQ = pe.field_tag(1, "varint")
    _PAYLOAD = pe.field_tag(2, "bytes")

    def encode_ping(seq, payload):
        body = _SEQ + pe.varint(seq) + _PAYLOAD + pe.uvarint(len(payload)) + payload
        return pe.message_field(T_PING, body)
    """


def test_a_tag_made_once_locks_like_the_helper_call():
    """`NAME = pe.field_tag(N, kind)` at module level, loaded in an
    encoder, is that encoder's field N — the same lockfile entry as the
    helper call it folds, and a renumbered constant is the same finding."""
    lock = wire_lock(WIRE_TREE)
    locked = lock["files"]["tendermint_tpu/proto1/messages.py"]["encoders"]
    assert locked["encode_ping"] == ["1:varint", "2:bytes", "T_PING=1:message"]
    folded = {"tendermint_tpu/proto1/messages.py": FOLDED_PING}
    got = wire_lock(dedent_tree(folded))["files"]["tendermint_tpu/proto1/messages.py"]
    assert got["encoders"] == locked  # nothing at "<module>", nothing lost
    assert [f for f in run_wire(folded, lock) if "encode_ping" in f.message] == []

    renumbered = {"tendermint_tpu/proto1/messages.py": FOLDED_PING.replace(
        'pe.field_tag(1, "varint")', 'pe.field_tag(6, "varint")')}
    fs = [f for f in run_wire(renumbered, lock) if "encode_ping" in f.message]
    assert len(fs) == 1
    assert "1:varint" in fs[0].message and "6:varint" in fs[0].message

    retyped = {"tendermint_tpu/proto1/messages.py": FOLDED_PING.replace(
        'pe.field_tag(2, "bytes")', 'pe.field_tag(2, "message")')}
    fs = [f for f in run_wire(retyped, lock) if "encode_ping" in f.message]
    assert len(fs) == 1 and "2:bytes" in fs[0].message and "2:message" in fs[0].message


def test_widened_wire_type_fails():
    lock = wire_lock(WIRE_TREE)
    mutated = {
        "tendermint_tpu/proto1/messages.py": WIRE_TREE[
            "tendermint_tpu/proto1/messages.py"
        ].replace("pe.varint_field(1, seq)", "pe.bytes_field(1, seq)")
    }
    fs = run_wire(mutated, lock)
    assert len(fs) == 1
    assert "1:varint" in fs[0].message and "1:bytes" in fs[0].message


def test_dropped_decode_bound_fails():
    lock = wire_lock(WIRE_TREE)
    src = WIRE_TREE["tendermint_tpu/proto1/messages.py"]
    # the named bound degrades to a magic number — the guard still
    # "works" today, but the schema lost its governing MAX_* constant
    src = src.replace(
        "if len(items) > MAX_ITEMS:", "if len(items) > 1073741824:"
    )
    assert "MAX_ITEMS:" not in src
    mutated = {"tendermint_tpu/proto1/messages.py": src}
    fs = run_wire(mutated, lock)
    assert any("DROPPED" in f.message and "MAX_ITEMS=64" in f.message for f in fs)


def test_reused_frame_tag_fails_without_lockfile_involvement():
    mutated = {
        "tendermint_tpu/proto1/messages.py": WIRE_TREE[
            "tendermint_tpu/proto1/messages.py"
        ]
        .replace("T_PONG = 2", "T_PONG = 1")
        .replace(
            "return pe.message_field(T_PING, body)",
            "return pe.message_field(T_PING, body)"
            ' + pe.message_field(T_PONG, b"")',
        )
    }
    # even a FRESH lock of the mutated tree cannot bless tag reuse
    lock = wire_lock(mutated)
    fs = run_wire(mutated, lock)
    assert any(
        "claimed by 2 constants" in f.message
        and "T_PING" in f.message
        and "T_PONG" in f.message
        for f in fs
    )


def test_channel_collision_across_files_fails():
    tree = dict(WIRE_TREE)
    tree["tendermint_tpu/proto2/messages.py"] = """
    from ..libs import protoenc as pe

    PROTO2_CHANNEL = 0x70

    def encode_x(v):
        return pe.varint_field(1, v)
    """
    lock = wire_lock(tree)
    fs = run_wire(tree, lock)
    assert any(
        "channel id 0x70" in f.message
        and "PROTO1_CHANNEL" in f.message
        and "PROTO2_CHANNEL" in f.message
        for f in fs
    )


def test_new_protoenc_file_without_lock_entry_is_a_finding():
    lock = wire_lock(WIRE_TREE)
    tree = dict(WIRE_TREE)
    tree["tendermint_tpu/proto3/fresh.py"] = """
    from ..libs import protoenc as pe

    def encode_y(v):
        return pe.varint_field(1, v)
    """
    fs = run_wire(tree, lock)
    assert any(
        f.path == "tendermint_tpu/proto3/fresh.py"
        and "no entry" in f.message
        for f in fs
    )


def test_stale_lock_entry_is_a_finding_only_on_full_tree():
    lock = wire_lock(WIRE_TREE)
    lock["files"]["tendermint_tpu/gone/old.py"] = {
        "encoders": {}, "decoders": {}, "bounds": []
    }
    fs = run_wire(WIRE_TREE, lock, full_tree=True)
    assert any("stale" in f.message for f in fs)
    # partial scans must not cry stale about files they did not look at
    assert run_wire(WIRE_TREE, lock, full_tree=False) == []


def test_channel_renumber_without_lock_update_fails():
    lock = wire_lock(WIRE_TREE)
    mutated = {
        "tendermint_tpu/proto1/messages.py": WIRE_TREE[
            "tendermint_tpu/proto1/messages.py"
        ].replace("PROTO1_CHANNEL = 0x70", "PROTO1_CHANNEL = 0x71")
    }
    fs = run_wire(mutated, lock)
    assert any("0x70 -> 0x71" in f.message for f in fs)


# ---------------------------------------------------------------------------
# the real tree: completeness + the messages.py renumber acceptance


def _real_tree_pctx() -> ProjectContext:
    from tendermint_tpu.tools.lint.cli import build_project_context

    return build_project_context(["tendermint_tpu"])


def test_lockfile_covers_every_protoenc_frame_family_in_the_tree():
    """Acceptance: a protoenc call site in a file absent from the
    lockfile is itself a finding (pinned by the fixture above), and the
    CHECKED-IN lockfile actually covers the tree at HEAD."""
    with open(LOCKFILE, encoding="utf-8") as f:
        lock = json.load(f)
    pctx = _real_tree_pctx()
    extracted = extract_wire_schema(pctx)
    missing = sorted(set(extracted["files"]) - set(lock.get("files", {})))
    assert missing == [], f"protoenc files not locked: {missing}"
    stale = sorted(set(lock.get("files", {})) - set(extracted["files"]))
    assert stale == [], f"stale lock entries: {stale}"
    # the frame families the tree grew over PRs 1-13 are all present
    for rel in (
        "tendermint_tpu/consensus/messages.py",
        "tendermint_tpu/consensus/wal.py",
        "tendermint_tpu/types/vote.py",
        "tendermint_tpu/types/block.py",
        "tendermint_tpu/types/evidence.py",
        "tendermint_tpu/types/part_set.py",
        "tendermint_tpu/types/params.py",
        "tendermint_tpu/types/validator_set.py",
        "tendermint_tpu/types/canonical.py",
        "tendermint_tpu/p2p/types.py",
        "tendermint_tpu/p2p/pex.py",
        "tendermint_tpu/p2p/secret.py",
        "tendermint_tpu/mempool/ingress.py",
        "tendermint_tpu/mempool/reactor.py",
        "tendermint_tpu/crypto/verifyd.py",
        "tendermint_tpu/light/fleet.py",
        "tendermint_tpu/abci/types.py",
        "tendermint_tpu/blocksync/messages.py",
        "tendermint_tpu/statesync/messages.py",
    ):
        assert rel in lock["files"], f"{rel} missing from lockfile"
        assert file_uses_protoenc(pctx, rel)


def test_renumbered_field_in_real_messages_py_fails_lint():
    """Acceptance: a one-line renumber in a fixture copy of the REAL
    consensus/messages.py fails against the REAL checked-in lockfile,
    with the old and new numbers in the message."""
    rel = "tendermint_tpu/consensus/messages.py"
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        source = f.read()
    needle = "pe.varint_field(2, msg.round + 1)"  # NewRoundStep.round
    assert needle in source
    mutated = source.replace(needle, "pe.varint_field(6, msg.round + 1)", 1)
    with open(LOCKFILE, encoding="utf-8") as f:
        lock = json.load(f)
    rules = [WireSchema(lock=lock)]
    fs = [
        f
        for f in lint_tree({rel: mutated}, rules, ALLOW, full_tree=False)
        if f.rule == "wire-schema"
    ]
    assert len(fs) == 1, [f.render() for f in fs]
    assert "2:varint" in fs[0].message and "6:varint" in fs[0].message
    assert "encode_message" in fs[0].message
    # and the unmutated copy is clean against the same lock
    assert [
        f
        for f in lint_tree({rel: source}, rules, ALLOW, full_tree=False)
        if f.rule == "wire-schema"
    ] == []


def test_real_tree_has_no_unpragmad_transitive_findings():
    """Acceptance: the full-tree scan is clean at HEAD for the
    interprocedural passes specifically (the whole-battery gate lives
    in test_lint.py; this pins the new rules with their own message)."""
    from tendermint_tpu.tools.lint import lint_paths

    findings, n = lint_paths(
        ["tendermint_tpu", "scripts"],
        [
            RULES_BY_ID["transitive-blocking"],
            RULES_BY_ID["transitive-verify"],
            RULES_BY_ID["transitive-fs"],
            RULES_BY_ID["transitive-cleanup"],
        ],
        ALLOW,
        report_pragma_errors=False,
    )
    assert n > 100
    assert findings == [], [f.render() for f in findings]


# ---------------------------------------------------------------------------
# CLI: --update-lock round-trip through the real entrypoint


def test_cli_update_lock_round_trip(tmp_path):
    """--update-lock writes a lockfile that the very next run is clean
    against (the blessing workflow), via the real entrypoint."""
    lock = tmp_path / "wire.lock.json"

    def tmtlint(*args):
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "tmtlint"), *args],
            cwd=REPO,
            capture_output=True,
            text=True,
            timeout=120,
        )

    out = tmtlint("--update-lock", "--lock", str(lock))
    assert out.returncode == 0, out.stdout + out.stderr
    assert "wire schema locked" in out.stdout
    written = json.loads(lock.read_text())
    assert written["files"] and written["channels"]
    out = tmtlint("--json", "--rule", "wire-schema", "--lock", str(lock),
                  "tendermint_tpu")
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["clean"] is True
    assert payload["per_rule"] == {"wire-schema": 0}
    # the tmp lock matches the checked-in one: --update-lock is
    # deterministic, so the blessing step never produces diff noise
    with open(LOCKFILE, encoding="utf-8") as f:
        assert written == json.load(f)


def test_wall_budget_for_project_passes():
    """The tree-wide passes (call graph + wire extraction) must stay a
    rounding error in the tier-1 budget — asserted via the same JSON
    the gate reads."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "tmtlint"), "--json"],
        cwd=REPO,
        capture_output=True,
        text=True,
        timeout=120,
    )
    payload = json.loads(out.stdout)
    assert payload["clean"] is True
    assert payload["elapsed_s"] < 10.0, f"lint too slow: {payload['elapsed_s']}s"
