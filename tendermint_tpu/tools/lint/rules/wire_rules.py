"""Wire-format analyzers: the protoenc frame layouts are consensus.

Every consensus-critical byte in this framework is produced by hand
against `libs/protoenc` — there is no codegen, so nothing structural
stops a refactor from renumbering `varint_field(2, msg.round)` to
field 6, reusing a frame type tag, or dropping the `MAX_*` clamp that
turns a corrupt varint into a ValueError instead of a multi-GiB
allocation. Each of those is a chain-splitting or DoS bug that no test
catches until two binary versions meet on a wire (fuzz can't see a
renumber: both sides of one build agree with themselves).

Two analyzers make the disciplines structural:

  * **wire-schema** (project rule): walks every protoenc call site in
    the tree and extracts a canonical schema per file — encode field
    lists (number:wiretype in source order, per function), decode tag
    sets, decode bounds in force, and the global channel-tag registry —
    then diffs it against the checked-in lockfile
    `tools/lint/wire_schema.lock.json`. Any drift (renumber, type
    change, dropped bound, new/retired frame file) fails lint until an
    intentional `scripts/tmtlint --update-lock` re-blesses it, which
    makes the lockfile diff the reviewable artifact of every wire
    change. Tag reuse inside a frame family and two channels claiming
    one id are findings regardless of the lockfile.

  * **wire-bounds** (per-file rule): a decode loop that grows a
    collection (or ranges over a decoded count) must be clamped by a
    named `MAX_*` bound in the same function — the PR 11
    allocation-bomb class (corrupt varint -> 2^40-entry request),
    enforced at the AST instead of remembered at review.
"""

from __future__ import annotations

import ast
import json
import os
import re
from typing import Iterable, Iterator

from ..framework import (
    FileContext,
    Finding,
    ProjectContext,
    ProjectRule,
    Rule,
    _same_frame_body,
    _same_frame_nodes,
)

LOCKFILE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "wire_schema.lock.json",
)
LOCKFILE_REL = "tendermint_tpu/tools/lint/wire_schema.lock.json"

#: dotted suffix identifying the codec module in resolved imports
_PROTOENC = "libs.protoenc"

#: encode helpers -> wire kind recorded in the schema
FIELD_HELPERS = {
    "varint_field": "varint",
    "bool_field": "varint",
    "sfixed64_field": "sfixed64",
    "fixed64_field": "fixed64",
    "bytes_field": "bytes",
    "string_field": "bytes",
    "message_field": "message",
    "tag": "tag",
}

#: `NAME = pe.field_tag(N, "kind")` at module level: a tag made once at
#: import. The encoder that loads NAME emits field N of that kind there,
#: and is locked exactly as if it had called the kind's helper.
TAG_HELPER = "field_tag"
_TAG_KINDS = frozenset(FIELD_HELPERS.values()) - {"tag"}

_MAX_NAME = re.compile(r"^_?MAX_[A-Z0-9_]+$|^[A-Z0-9_]+_MAX$")
_CHANNEL_NAME = re.compile(r"^[A-Z0-9_]*_CHANNEL$")


def _qualname(ctx: FileContext, node: ast.AST) -> str:
    """Innermost enclosing function, prefixed with its class when the
    def sits directly in a ClassDef; module-level sites -> "<module>"."""
    fn = ctx.enclosing_function(node)
    if fn is None:
        return "<module>"
    parent = ctx.parents.get(fn)
    if isinstance(parent, ast.ClassDef):
        return f"{parent.name}.{fn.name}"
    return fn.name


def _bound_name(node: ast.expr) -> str | None:
    if isinstance(node, ast.Name) and _MAX_NAME.match(node.id):
        return node.id
    if isinstance(node, ast.Attribute) and _MAX_NAME.match(node.attr):
        return node.attr
    return None


class _FileWire:
    """Extracted wire surface of one file."""

    def __init__(self) -> None:
        self.encoders: dict[str, list[tuple[int, int, str]]] = {}
        # qualname -> [(lineno, col, "field:kind")], sorted before render
        self.decoders: dict[str, dict[str, float]] = {}
        # qualname -> {repr: sort_value}
        self.bounds: set[str] = set()
        self.tag_names: dict[str, tuple[int, int]] = {}
        # constant NAME used in wire-tag position -> (value, first lineno)

    def render(self) -> dict:
        enc = {
            fn: [e[2] for e in sorted(entries)]
            for fn, entries in sorted(self.encoders.items())
        }
        dec = {
            fn: [r for r, _ in sorted(reprs.items(), key=lambda kv: (kv[1], kv[0]))]
            for fn, reprs in sorted(self.decoders.items())
        }
        return {
            "encoders": enc,
            "decoders": dec,
            "bounds": sorted(self.bounds),
        }


def _field_repr(pctx: ProjectContext, rel: str, node: ast.expr) -> tuple[str, float]:
    """(repr, numeric sort key) of a wire tag/field-number expression:
    `3` -> ("3", 3); `T_VOTE` -> ("T_VOTE=6", 6); unresolvable ->
    ("<expr>", inf) — still deterministic, still diffable."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return str(node.value), float(node.value)
    name = None
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    if name is not None:
        resolved = pctx.resolve_constant(rel, name)
        if resolved is not None:
            return f"{resolved[0]}={resolved[1]}", float(resolved[1])
        return f"<{name}>", float("inf")
    return "<expr>", float("inf")


def _pe_helper(
    pctx: ProjectContext, rel: str, node: ast.Call, names=FIELD_HELPERS
) -> str | None:
    """The protoenc encode helper a call resolves to, if any: matches
    `pe.varint_field(...)` through a module alias bound to
    libs/protoenc, and bare `varint_field(...)` through a from-import
    of the helper itself."""
    imports = pctx.imports_of(rel)
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        target = imports.get(f.value.id, "")
        if target.endswith(_PROTOENC) and f.attr in names:
            return f.attr
    elif isinstance(f, ast.Name):
        target = imports.get(f.id, "")
        head, _, helper = target.rpartition(".")
        if head.endswith(_PROTOENC) and helper in names:
            return helper
    return None


def _folded_tags(pctx: ProjectContext, rel: str) -> dict[str, str]:
    """Module-level tag constants of `rel`: NAME -> "field:kind"."""
    out: dict[str, str] = {}
    for stmt in pctx.files[rel].tree.body:
        if not (
            isinstance(stmt, ast.Assign)
            and len(stmt.targets) == 1
            and isinstance(stmt.targets[0], ast.Name)
            and isinstance(stmt.value, ast.Call)
            and len(stmt.value.args) == 2
            and _pe_helper(pctx, rel, stmt.value, (TAG_HELPER,))
        ):
            continue
        number, kind = stmt.value.args
        if isinstance(kind, ast.Constant) and kind.value in _TAG_KINDS:
            repr_, _sort = _field_repr(pctx, rel, number)
            out[stmt.targets[0].id] = f"{repr_}:{kind.value}"
    return out


def file_uses_protoenc(pctx: ProjectContext, rel: str) -> bool:
    if not rel.startswith("tendermint_tpu/") or rel == f"tendermint_tpu/{_PROTOENC.replace('.', '/')}.py":
        return False
    return any(
        t == f"tendermint_tpu.{_PROTOENC}"
        or t.startswith(f"tendermint_tpu.{_PROTOENC}.")
        or t.endswith(_PROTOENC)
        for t in pctx.imports_of(rel).values()
    )


def _tag_vars(fn_nodes: list[ast.AST]) -> set[str]:
    """Names bound from `f, wt = r.read_tag()` in a frame."""
    out: set[str] = set()
    for node in fn_nodes:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Tuple)
            and len(node.targets[0].elts) == 2
            and isinstance(node.targets[0].elts[0], ast.Name)
            and isinstance(node.value, ast.Call)
            and isinstance(node.value.func, ast.Attribute)
            and node.value.func.attr == "read_tag"
        ):
            out.add(node.targets[0].elts[0].id)
    return out


def extract_file_wire(pctx: ProjectContext, rel: str) -> _FileWire | None:
    """Walk one file's protoenc surface. None when the file does not
    touch the codec."""
    if not file_uses_protoenc(pctx, rel):
        return None
    ctx = pctx.files[rel]
    wire = _FileWire()

    def note_tag_name(node: ast.expr) -> None:
        name = None
        if isinstance(node, ast.Name):
            name = node.id
        elif isinstance(node, ast.Attribute):
            name = node.attr
        if name is None:
            return
        resolved = pctx.resolve_constant(rel, name)
        if resolved is not None and name not in wire.tag_names:
            wire.tag_names[name] = (resolved[1], node.lineno)

    # -- encode side ----------------------------------------------------
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        helper = _pe_helper(pctx, rel, node)
        if helper is None or not node.args:
            continue
        field = node.args[0]
        repr_, _sort = _field_repr(pctx, rel, field)
        note_tag_name(field)
        qn = _qualname(ctx, node)
        wire.encoders.setdefault(qn, []).append(
            (node.lineno, node.col_offset, f"{repr_}:{FIELD_HELPERS[helper]}")
        )

    folded = _folded_tags(pctx, rel)
    if folded:
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in folded
                and ctx.enclosing_function(node) is not None
            ):
                wire.encoders.setdefault(_qualname(ctx, node), []).append(
                    (node.lineno, node.col_offset, folded[node.id])
                )

    # -- decode side ----------------------------------------------------
    funcs: list[tuple[str, list[ast.AST]]] = []
    for n in ast.walk(ctx.tree):
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
            parent = ctx.parents.get(n)
            qn = (
                f"{parent.name}.{n.name}"
                if isinstance(parent, ast.ClassDef)
                else n.name
            )
            funcs.append((qn, list(_same_frame_nodes(n))))
    funcs.append(("<module>", [n for n in ast.walk(ctx.tree)
                               if ctx.enclosing_function(n) is None]))
    for qn, nodes in funcs:
        tagvars = _tag_vars(nodes)
        for node in nodes:
            if isinstance(node, ast.Compare):
                # decode tag dispatch: `f == T_X` / `f in (T_A, T_B)`
                if (
                    tagvars
                    and isinstance(node.left, ast.Name)
                    and node.left.id in tagvars
                    and len(node.ops) == 1
                ):
                    comps: list[ast.expr] = []
                    if isinstance(node.ops[0], ast.Eq):
                        comps = [node.comparators[0]]
                    elif isinstance(node.ops[0], ast.In) and isinstance(
                        node.comparators[0], (ast.Tuple, ast.List, ast.Set)
                    ):
                        comps = list(node.comparators[0].elts)
                    for c in comps:
                        repr_, sort = _field_repr(pctx, rel, c)
                        note_tag_name(c)
                        wire.decoders.setdefault(qn, {})[repr_] = sort
                # bound guards in force: `x > MAX_Y` / `MAX_Y < x`
                for side in (node.left, *node.comparators):
                    bname = _bound_name(side)
                    if bname is not None:
                        resolved = pctx.resolve_constant(rel, bname)
                        val = resolved[1] if resolved else "?"
                        wire.bounds.add(f"{bname}={val}")
            elif isinstance(node, ast.Call):
                # `min(n, MAX_Y)` clamps and `_check_x(lst, MAX_Y, ...)`
                # shared checkers count as bounds too — same contract as
                # the wire-bounds guard detection
                for a in node.args:
                    bname = _bound_name(a)
                    if bname is not None:
                        resolved = pctx.resolve_constant(rel, bname)
                        val = resolved[1] if resolved else "?"
                        wire.bounds.add(f"{bname}={val}")
    return wire


def extract_channels(pctx: ProjectContext) -> dict[str, dict]:
    """Tree-wide channel-tag registry: every module-level
    `*_CHANNEL = <int>` under tendermint_tpu/."""
    out: dict[str, dict] = {}
    for rel in sorted(pctx.files):
        if not rel.startswith("tendermint_tpu/"):
            continue
        for name, value in pctx.constants_of(rel).items():
            if _CHANNEL_NAME.match(name):
                out[name] = {"value": value, "file": rel}
    return out


def extract_wire_schema(pctx: ProjectContext) -> dict:
    """The full canonical schema — what --update-lock writes and the
    wire-schema rule diffs against the lockfile."""
    files: dict[str, dict] = {}
    for rel in sorted(pctx.files):
        wire = extract_file_wire(pctx, rel)
        if wire is None:
            continue
        rendered = wire.render()
        if not (rendered["encoders"] or rendered["decoders"]):
            continue  # imports the codec but defines no frames (re-export)
        files[rel] = rendered
    return {
        "version": 1,
        "channels": extract_channels(pctx),
        "files": files,
    }


def load_lockfile(path: str = LOCKFILE) -> dict | None:
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def write_lockfile(schema: dict, path: str = LOCKFILE) -> None:
    with open(path, "w", encoding="utf-8") as f:
        json.dump(schema, f, indent=2, sort_keys=True)
        f.write("\n")


def _diff_encoder(old: list[str], new: list[str]) -> str | None:
    if old == new:
        return None
    if len(old) == len(new):
        changes = [
            f"{o} -> {n}" for o, n in zip(old, new) if o != n
        ]
        return "field renumbered/retyped: " + "; ".join(changes)
    return (
        f"field list changed ({len(old)} -> {len(new)} fields): "
        f"{old} -> {new}"
    )


def _diff_decoder(old: list[str], new: list[str]) -> str | None:
    if old == new:
        return None
    removed = [t for t in old if t not in new]
    added = [t for t in new if t not in old]
    parts = []
    if removed:
        parts.append(f"tags no longer decoded: {removed}")
    if added:
        parts.append(f"new tags decoded: {added}")
    return "decode tag set changed — " + "; ".join(parts)


class WireSchema(ProjectRule):
    id = "wire-schema"
    doc = (
        "every protoenc frame layout (field numbers, wire types, decode "
        "tag sets, decode bounds, channel ids) must match the checked-in "
        "tools/lint/wire_schema.lock.json — a renumber/type-change/"
        "dropped-bound fails lint until `scripts/tmtlint --update-lock` "
        "re-blesses it; frame-tag reuse and two channels on one id are "
        "findings unconditionally"
    )
    profiles = ("node",)

    def __init__(self, lock: dict | None = None, lock_path: str = LOCKFILE):
        #: injected lockfile dict (tests); None -> load from lock_path
        self._lock_override = lock
        self._lock_path = lock_path

    def _lock(self) -> dict | None:
        if self._lock_override is not None:
            return self._lock_override
        return load_lockfile(self._lock_path)

    def check_project(self, pctx: ProjectContext) -> Iterable[Finding]:
        extracted_files: dict[str, _FileWire] = {}
        for rel in sorted(pctx.files):
            wire = extract_file_wire(pctx, rel)
            if wire is not None:
                extracted_files[rel] = wire

        # -- unconditional structural checks ---------------------------
        yield from self._check_tag_reuse(pctx, extracted_files)
        if pctx.full_tree:
            yield from self._check_channel_collisions(pctx)

        lock = self._lock()
        if lock is None:
            if extracted_files:
                first = sorted(extracted_files)[0]
                yield Finding(
                    self.id,
                    first,
                    1,
                    1,
                    "no wire-schema lockfile found "
                    f"({LOCKFILE_REL}) but the tree has protoenc call "
                    "sites — run `scripts/tmtlint --update-lock` to "
                    "create it",
                )
            return

        lock_files: dict = lock.get("files", {})
        for rel in sorted(extracted_files):
            rendered = extracted_files[rel].render()
            if not (rendered["encoders"] or rendered["decoders"]):
                continue
            locked = lock_files.get(rel)
            if locked is None:
                yield Finding(
                    self.id,
                    rel,
                    1,
                    1,
                    "file has protoenc encode/decode sites but no entry "
                    f"in {LOCKFILE_REL} — every frame family must be "
                    "locked; run `scripts/tmtlint --update-lock` and "
                    "review the diff",
                )
                continue
            yield from self._diff_file(rel, locked, rendered)

        if pctx.full_tree:
            for rel in sorted(lock_files):
                if rel not in extracted_files:
                    yield Finding(
                        self.id,
                        LOCKFILE_REL,
                        1,
                        1,
                        f"lockfile entry for {rel} is stale (file gone or "
                        "no longer touches protoenc) — run "
                        "`scripts/tmtlint --update-lock`",
                    )
            yield from self._diff_channels(pctx, lock.get("channels", {}))

    # -- helpers --------------------------------------------------------

    def _diff_file(
        self, rel: str, locked: dict, rendered: dict
    ) -> Iterator[Finding]:
        for section, differ in (
            ("encoders", _diff_encoder),
            ("decoders", _diff_decoder),
        ):
            old_s: dict = locked.get(section, {})
            new_s: dict = rendered[section]
            for fn in sorted(set(old_s) | set(new_s)):
                if fn not in new_s:
                    yield Finding(
                        self.id, rel, 1, 1,
                        f"locked {section[:-1]} `{fn}` no longer exists — "
                        "wire surface shrank; --update-lock after review",
                    )
                elif fn not in old_s:
                    yield Finding(
                        self.id, rel, 1, 1,
                        f"new {section[:-1]} `{fn}` is not in the lockfile "
                        "— new frame family; --update-lock after review",
                    )
                else:
                    msg = differ(old_s[fn], new_s[fn])
                    if msg:
                        yield Finding(
                            self.id, rel, 1, 1,
                            f"`{fn}` drifted from {LOCKFILE_REL}: {msg} — "
                            "a wire break unless both sides upgrade in "
                            "lockstep; if intentional, run "
                            "`scripts/tmtlint --update-lock` and ship the "
                            "lockfile diff with the change",
                        )
        old_b = locked.get("bounds", [])
        new_b = rendered["bounds"]
        if old_b != new_b:
            dropped = [b for b in old_b if b not in new_b]
            added = [b for b in new_b if b not in old_b]
            parts = []
            if dropped:
                parts.append(
                    f"decode bounds DROPPED: {dropped} (the corrupt-varint "
                    "allocation-bomb guard class)"
                )
            if added:
                parts.append(f"bounds added: {added}")
            yield Finding(
                self.id, rel, 1, 1,
                "decode-bound set drifted: " + "; ".join(parts) +
                " — --update-lock only if the bound moved on purpose",
            )

    def _check_tag_reuse(
        self, pctx: ProjectContext, extracted: dict[str, _FileWire]
    ) -> Iterator[Finding]:
        for rel in sorted(extracted):
            wire = extracted[rel]
            by_family: dict[tuple[str, int], list[tuple[int, str]]] = {}
            for name, (value, line) in wire.tag_names.items():
                family = name.split("_", 1)[0]
                by_family.setdefault((family, value), []).append((line, name))
            for (family, value), names in sorted(by_family.items()):
                if len(names) < 2:
                    continue
                names.sort()
                listed = ", ".join(n for _, n in names)
                yield Finding(
                    self.id,
                    rel,
                    names[1][0],
                    1,
                    f"wire tag value {value} is claimed by {len(names)} "
                    f"constants in the {family}_* family ({listed}) — two "
                    "frame types on one tag decode as each other; "
                    "renumber one and --update-lock",
                )

    def _check_channel_collisions(
        self, pctx: ProjectContext
    ) -> Iterator[Finding]:
        claims: dict[int, list[tuple[str, str]]] = {}
        for name, info in extract_channels(pctx).items():
            claims.setdefault(info["value"], []).append((name, info["file"]))
        for value, names in sorted(claims.items()):
            if len(names) < 2:
                continue
            names.sort()
            listed = ", ".join(f"{n} ({f})" for n, f in names)
            yield Finding(
                self.id,
                names[1][1],
                1,
                1,
                f"channel id 0x{value:02x} is claimed by {len(names)} frame "
                f"families: {listed} — the router demuxes by channel id, so "
                "two reactors on one id feed each other's decoder; pick a "
                "free id (see the channels table in the lockfile)",
            )

    def _diff_channels(
        self, pctx: ProjectContext, locked: dict
    ) -> Iterator[Finding]:
        current = extract_channels(pctx)
        for name in sorted(set(locked) | set(current)):
            old = locked.get(name)
            new = current.get(name)
            if old is None:
                yield Finding(
                    self.id, new["file"], 1, 1,
                    f"new channel constant {name}=0x{new['value']:02x} is "
                    "not in the lockfile — --update-lock after review",
                )
            elif new is None:
                yield Finding(
                    self.id, LOCKFILE_REL, 1, 1,
                    f"locked channel {name} is gone — --update-lock",
                )
            elif old["value"] != new["value"]:
                yield Finding(
                    self.id, new["file"], 1, 1,
                    f"channel {name} renumbered 0x{old['value']:02x} -> "
                    f"0x{new['value']:02x} without a lockfile update — a "
                    "mixed-version net demuxes the old id into the wrong "
                    "reactor; --update-lock only with a coordinated "
                    "rollout plan",
                )


class WireBounds(Rule):
    id = "wire-bounds"
    doc = (
        "a protoenc decode loop that grows a collection or ranges over a "
        "decoded count must clamp it with a named MAX_* bound in the "
        "same function — a corrupt varint is attacker-controlled "
        "allocation otherwise (the PR 11 corrupt-frame bomb class)"
    )
    scope = ("tendermint_tpu/",)
    profiles = ("node",)

    GROWTH_METHODS = {"append", "extend", "appendleft", "add", "insert"}

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        if ctx.rel == "tendermint_tpu/libs/protoenc.py":
            return  # the codec itself: Reader slices its own buffer
        for fn in self._functions(ctx):
            nodes = list(_same_frame_nodes(fn))
            loops = [
                n
                for n in nodes
                if isinstance(n, ast.While) and self._is_reader_loop(n)
            ]
            if not loops:
                continue
            if self._has_bound_guard(nodes):
                continue
            # nested reader loops (message-in-message decodes) both walk
            # the inner sites — dedup by position
            seen: set[tuple[int, int]] = set()
            for loop in loops:
                for site, what in self._risk_sites(loop):
                    pos = (site.lineno, site.col_offset)
                    if pos in seen:
                        continue
                    seen.add(pos)
                    yield ctx.finding(
                        self.id,
                        site,
                        f"{what} inside a wire decode loop with no named "
                        "MAX_* clamp anywhere in this function: a corrupt "
                        "count/length varint becomes an unbounded "
                        "allocation (the RouterNet corrupt-frame bomb "
                        "class); add `if len(...) > MAX_<THING>: raise "
                        "ValueError(...)` with a module-level bound",
                    )

    @staticmethod
    def _functions(ctx: FileContext) -> Iterator[ast.AST]:
        for node in ast.walk(ctx.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield node

    @staticmethod
    def _is_reader_loop(node: ast.While) -> bool:
        t = node.test
        return (
            isinstance(t, ast.UnaryOp)
            and isinstance(t.op, ast.Not)
            and isinstance(t.operand, ast.Call)
            and isinstance(t.operand.func, ast.Attribute)
            and t.operand.func.attr == "eof"
        )

    def _risk_sites(self, loop: ast.While) -> Iterator[tuple[ast.AST, str]]:
        for node in _same_frame_body(loop.body):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr in self.GROWTH_METHODS
            ):
                yield node, f"`.{f.attr}(...)` growth"
            elif isinstance(f, ast.Name) and f.id == "range":
                if any(
                    isinstance(sub, ast.Call)
                    and isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "read_uvarint"
                    for a in node.args
                    for sub in ast.walk(a)
                ):
                    yield node, "`range(<decoded count>)` iteration"

    @staticmethod
    def _has_bound_guard(nodes: list[ast.AST]) -> bool:
        for node in nodes:
            if isinstance(node, ast.Compare):
                if any(
                    _bound_name(side) is not None
                    for side in (node.left, *node.comparators)
                ):
                    return True
            elif isinstance(node, ast.Call) and any(
                _bound_name(a) is not None for a in node.args
            ):
                # min(n, MAX_X) clamps; so does handing the bound to a
                # shared checker (`_check_repeat(lst, MAX_X, ...)`) —
                # what matters is that a NAMED bound governs the site
                return True
        return False


RULES = (WireSchema(), WireBounds())
