"""WireGen drift analyzer: generated codec and wire schema are one.

`tools/wiregen` compiles the hot consensus codec
(`consensus/wire_gen.py`) from the blessed wire-schema lockfile. That
only stays safe while three artifacts agree: the lockfile, wiregen's
spec tables, and the checked-in generated module. This rule makes the
agreement structural, the same way wire-schema pins the interpreted
codec:

  * regenerate the module IN MEMORY from the lockfile and fail unless
    the checked-in `consensus/wire_gen.py` is byte-identical — so a
    hand edit of generated code, a lockfile re-bless without
    `scripts/wiregen --update`, or a spec-table change that was not
    propagated all fail lint with the one command that fixes them;
  * a `SpecMismatch` (lockfile and spec tables disagree about a frame
    layout) is itself a finding: the tree's wire surface moved and the
    compiler was not taught the new layout;
  * raw calls to the interpreted `encode_message_py` /
    `decode_message_py` outside the codec-owning modules are findings —
    call sites must go through the rebindable `encode_message` /
    `decode_message` dispatch so the generated fast path (and its
    `TMTPU_WIREGEN=0` kill switch) actually governs the hot loop.
"""

from __future__ import annotations

import ast
from typing import Iterable, Iterator

from ...wiregen.generator import (
    GENERATED_REL,
    LOCKFILE_REL,
    SpecMismatch,
    generate,
    load_lock,
)
from ..framework import Finding, ProjectContext, ProjectRule, call_name

#: interpreted entry points that only the codec owners may name
_RAW_CODEC = ("encode_message_py", "decode_message_py")

#: files allowed to touch the interpreted entry points directly: the
#: owning module, the generated module's fallback path, the toolchain
#: that compiles/verifies them, and tests (which pin A/B parity)
_RAW_ALLOWED_PREFIXES = (
    "tendermint_tpu/tools/",
    "tests/",
)
_RAW_ALLOWED_FILES = frozenset(
    {
        "tendermint_tpu/consensus/messages.py",
        GENERATED_REL,
    }
)


def _raw_call_allowed(rel: str) -> bool:
    return rel in _RAW_ALLOWED_FILES or rel.startswith(_RAW_ALLOWED_PREFIXES)


class WiregenDrift(ProjectRule):
    id = "wiregen-drift"
    doc = (
        "consensus/wire_gen.py must be byte-identical to an in-memory "
        "regen from tools/lint/wire_schema.lock.json (hand edits and "
        "un-regenerated lockfile changes fail; fix with "
        "`scripts/wiregen --update`), and call sites outside the codec "
        "owners must use the encode_message/decode_message dispatch, "
        "never the raw interpreted *_py entry points"
    )
    profiles = ("node",)

    def __init__(self, lock: dict | None = None, lock_path: str | None = None):
        #: injected lockfile dict (tests); None -> load from lock_path
        self._lock_override = lock
        self._lock_path = lock_path

    def _lock(self) -> dict | None:
        if self._lock_override is not None:
            return self._lock_override
        try:
            return load_lock(self._lock_path)
        except (OSError, ValueError):
            return None

    def check_project(self, pctx: ProjectContext) -> Iterable[Finding]:
        yield from self._check_raw_calls(pctx)
        yield from self._check_drift(pctx)

    # -- generated-module freshness -------------------------------------

    def _check_drift(self, pctx: ProjectContext) -> Iterator[Finding]:
        gen_ctx = pctx.files.get(GENERATED_REL)
        if gen_ctx is None and not pctx.full_tree:
            # partial scan without the generated module: nothing to pin
            return
        lock = self._lock()
        if lock is None:
            yield Finding(
                self.id,
                GENERATED_REL if gen_ctx is not None else LOCKFILE_REL,
                1,
                1,
                f"cannot load {LOCKFILE_REL} but the tree carries a "
                "generated codec — restore the lockfile (or re-bless "
                "with `scripts/tmtlint --update-lock`) before linting "
                "the generated module",
            )
            return
        try:
            fresh = generate(lock)
        except SpecMismatch as exc:
            yield Finding(
                self.id,
                LOCKFILE_REL,
                1,
                1,
                f"wiregen spec mismatch: {exc}",
            )
            return
        if gen_ctx is None:
            yield Finding(
                self.id,
                GENERATED_REL,
                1,
                1,
                f"{GENERATED_REL} is missing but the lockfile compiles "
                "cleanly — run `scripts/wiregen --update` and check the "
                "generated module in",
            )
            return
        if gen_ctx.source != fresh:
            yield Finding(
                self.id,
                GENERATED_REL,
                1,
                1,
                f"{GENERATED_REL} is not byte-identical to a fresh "
                f"regen from {LOCKFILE_REL} (hand edit, or a wire "
                "change was blessed without regenerating) — run "
                "`scripts/wiregen --update`",
            )

    # -- raw interpreted-codec calls ------------------------------------

    def _check_raw_calls(self, pctx: ProjectContext) -> Iterator[Finding]:
        for rel in sorted(pctx.files):
            if _raw_call_allowed(rel):
                continue
            ctx = pctx.files[rel]
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name is None:
                    continue
                leaf = name.rpartition(".")[2]
                if leaf not in _RAW_CODEC:
                    continue
                yield Finding(
                    self.id,
                    rel,
                    node.lineno,
                    node.col_offset + 1,
                    f"raw interpreted codec call `{name}` — dispatch "
                    "through encode_message/decode_message so the "
                    "generated fast path (and the TMTPU_WIREGEN kill "
                    "switch) governs this call site",
                )


RULES = (WiregenDrift(),)
