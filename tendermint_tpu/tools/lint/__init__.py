"""tmtlint — project-specific static analysis for tendermint_tpu.

Public surface:

  * `ALL_RULES` / `RULES_BY_ID` — the analyzer battery
  * `lint_paths` / `lint_source` — run rules over files or a source blob
  * `Finding`, `Rule`, `FileContext`, `Allowlist` — extension points

Driver: `scripts/tmtlint` (text/JSON output, --rule, --changed,
--update-lock).
Invariant docs: README "Static analysis".
"""

from .framework import (  # noqa: F401
    BAD_PRAGMA,
    DEFAULT_ALLOWLIST,
    REPO,
    Allowlist,
    FileContext,
    Finding,
    ProjectContext,
    ProjectRule,
    Rule,
    lint_paths,
    lint_source,
    lint_tree,
)
from .rules import ALL_RULES, RULES_BY_ID  # noqa: F401
