"""tmtlint driver — run the project's AST invariant analyzers.

Usage (via the `scripts/tmtlint` entrypoint):
    scripts/tmtlint                          # whole tree (tier-1 gate)
    scripts/tmtlint --rule clock-discipline tendermint_tpu/consensus
    scripts/tmtlint --changed                # only git-modified files
    scripts/tmtlint --json                   # machine output (+ wall time,
                                             #   per-rule finding counts)
    scripts/tmtlint --update-lock            # re-bless the wire schema
    scripts/tmtlint --list-rules

Exit status: 0 clean, 1 findings, 2 usage/internal error.

One code path for every consumer: the tier-1 gate (tests/test_lint.py)
shells out to `scripts/tmtlint --json` and pre-commit runs `--changed`
— there is no second driver to drift.

`--changed` analyzes the FULL default surface (the project rules need
the whole tree: an interprocedural chain or a wire-schema diff does not
stop at your diff). Per-file findings are reported only for files
modified vs HEAD plus untracked; PROJECT-rule findings are reported
wherever they land — a transitive chain your edit created surfaces at a
coroutine you did not touch, and a retired frame file surfaces at the
lockfile. The tier-1 gate keeps the tree clean, so any project finding
under --changed is a consequence of the change in hand, never
pre-existing debt.

The rules, pragma syntax (`# tmtlint: allow[rule] -- reason`), the
checked-in allowlist and the wire-schema lockfile live in
tendermint_tpu/tools/lint/; see the README "Static analysis" section
for the invariant behind each rule.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from collections import Counter

from .framework import (
    DEFAULT_ALLOWLIST,
    REPO,
    Allowlist,
    FileContext,
    ProjectContext,
    _parse_context,
    iter_py_files,
    lint_paths,
)
from .rules import ALL_RULES, RULES_BY_ID
from .rules.wire_rules import (
    LOCKFILE,
    extract_wire_schema,
    write_lockfile,
)

DEFAULT_PATHS = ["tendermint_tpu", "scripts", "tests"]


def changed_files() -> list[str]:
    """Working-tree changes vs HEAD plus untracked files — the fast
    pre-commit surface."""
    out = subprocess.run(
        ["git", "diff", "--name-only", "HEAD"],
        cwd=REPO,
        capture_output=True,
        text=True,
        check=True,
    ).stdout.splitlines()
    untracked = subprocess.run(
        ["git", "ls-files", "--others", "--exclude-standard"],
        cwd=REPO,
        capture_output=True,
        text=True,
        check=True,
    ).stdout.splitlines()
    return [
        p
        for p in dict.fromkeys(out + untracked)
        if p.endswith(".py") and os.path.exists(os.path.join(REPO, p))
    ]


def build_project_context(
    paths: list[str] | None = None, repo: str = REPO
) -> ProjectContext:
    """Parse the scan surface into a ProjectContext (used by
    --update-lock and by tests that want the extractor directly)."""
    files: dict[str, FileContext] = {}
    for rel in iter_py_files(paths or DEFAULT_PATHS, repo):
        with open(os.path.join(repo, rel), encoding="utf-8") as f:
            source = f.read()
        ctx = _parse_context(source, rel)
        if isinstance(ctx, FileContext):
            files[rel] = ctx
    return ProjectContext(files, full_tree=True)


def _emit_json(
    findings, n_files: int, rules, elapsed: float
) -> dict:
    per_rule = Counter(f.rule for f in findings)
    return {
        "findings": [f.to_json() for f in findings],
        "files_scanned": n_files,
        "rules": [r.id for r in rules],
        # per-rule finding counts (zeros included) + wall time: the
        # BENCH rounds diff these across PRs to watch lint drift
        "per_rule": {r.id: per_rule.get(r.id, 0) for r in rules},
        "elapsed_s": round(elapsed, 3),
        "clean": not findings,
    }


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help=f"files/dirs (default: {DEFAULT_PATHS})")
    ap.add_argument(
        "--rule",
        action="append",
        default=[],
        metavar="ID",
        help="run only these rule ids (repeatable)",
    )
    ap.add_argument("--json", action="store_true", help="machine-readable output")
    ap.add_argument(
        "--changed",
        action="store_true",
        help="per-file findings only for files modified vs HEAD (plus "
        "untracked); project rules analyze the full surface and report "
        "wherever their findings land, so cross-file consequences of "
        "the change are never missed",
    )
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument(
        "--allowlist",
        default=DEFAULT_ALLOWLIST,
        help="path to the allowlist JSON (default: checked-in)",
    )
    ap.add_argument(
        "--update-lock",
        action="store_true",
        help="re-extract the wire schema from the tree and write the "
        "lockfile — the explicit blessing step for an intentional wire "
        "change (ship the lockfile diff with it)",
    )
    ap.add_argument(
        "--lock",
        default=LOCKFILE,
        help="path of the wire-schema lockfile (default: checked-in)",
    )
    args = ap.parse_args(argv)

    if args.list_rules:
        for r in ALL_RULES:
            scope = ", ".join(r.scope) if r.scope else "everywhere"
            print(f"{r.id:22s} [{'/'.join(r.profiles)}] {r.doc}")
            print(f"{'':22s} scope: {scope}")
        return 0

    if args.update_lock:
        pctx = build_project_context(["tendermint_tpu"])
        schema = extract_wire_schema(pctx)
        write_lockfile(schema, args.lock)
        n_frames = sum(
            len(e.get("encoders", {})) + len(e.get("decoders", {}))
            for e in schema["files"].values()
        )
        print(
            f"tmtlint: wire schema locked — {len(schema['files'])} files, "
            f"{n_frames} frame functions, {len(schema['channels'])} "
            f"channels -> {os.path.relpath(args.lock, REPO)}"
        )
        return 0

    rules = list(ALL_RULES)
    if args.rule:
        unknown = [r for r in args.rule if r not in RULES_BY_ID]
        if unknown:
            print(f"unknown rule id(s): {', '.join(unknown)}", file=sys.stderr)
            print(f"known: {', '.join(sorted(RULES_BY_ID))}", file=sys.stderr)
            return 2
        rules = [RULES_BY_ID[r] for r in args.rule]

    # non-default lockfile: rebind the wire-schema rule instance
    if args.lock != LOCKFILE:
        from .rules.wire_rules import WireSchema

        rules = [
            WireSchema(lock_path=args.lock) if r.id == "wire-schema" else r
            for r in rules
        ]

    # a typo'd path must be a usage error, not a 0-file "clean" — the
    # silent-miss class this linter exists to prevent
    missing = [
        p
        for p in args.paths
        if not os.path.exists(p if os.path.isabs(p) else os.path.join(REPO, p))
    ]
    if missing:
        print(f"no such path(s): {', '.join(missing)}", file=sys.stderr)
        return 2

    restrict = None
    paths = args.paths or DEFAULT_PATHS
    if args.changed:
        # intersect with the gate's scan surface (or the named paths):
        # pre-commit must never fail on files the tier-1 gate ignores,
        # or pass on files it checks
        scope = [
            os.path.relpath(p, REPO).replace(os.sep, "/")
            if os.path.isabs(p)
            else p.rstrip("/")
            for p in (args.paths or DEFAULT_PATHS)
        ]
        restrict = [
            f
            for f in changed_files()
            if any(f == s or f.startswith(s + "/") for s in scope)
        ]
        if not restrict:
            if args.json:
                print(json.dumps(_emit_json([], 0, rules, 0.0)))
            else:
                print("tmtlint: no changed python files")
            return 0

    allowlist = Allowlist.load(args.allowlist)
    t0 = time.monotonic()
    # bad-pragma findings belong to the full gate; a single-rule run
    # (the shims, --rule spot checks) reports only its own rule
    findings, n_files = lint_paths(
        paths,
        rules,
        allowlist,
        REPO,
        report_pragma_errors=not args.rule,
        known_rules=set(RULES_BY_ID),
        restrict_to=restrict,
    )
    elapsed = time.monotonic() - t0

    if args.json:
        print(json.dumps(_emit_json(findings, n_files, rules, elapsed), indent=2))
        return 1 if findings else 0

    if not findings:
        print(
            f"tmtlint: clean — {n_files} files, {len(rules)} rules, "
            f"{elapsed * 1e3:.0f} ms"
        )
        return 0
    print(
        f"tmtlint: {len(findings)} finding(s) across {n_files} files "
        f"({elapsed * 1e3:.0f} ms):",
        file=sys.stderr,
    )
    for f in findings:
        print(f"  {f.render()}", file=sys.stderr)
        if f.snippet:
            print(f"      {f.snippet}", file=sys.stderr)
    print(
        "\nfix the call site, or annotate an intentional one with\n"
        "  # tmtlint: allow[rule-id] -- reason\n"
        "(wire-schema drift: `scripts/tmtlint --update-lock` blesses an\n"
        "intentional wire change; see README 'Static analysis')",
        file=sys.stderr,
    )
    return 1


if __name__ == "__main__":
    sys.exit(main())
