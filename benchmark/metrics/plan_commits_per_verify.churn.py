"""plan_commits_per_verify.churn

Commits a verify call carried: Σ `planned` over the window's `blocksync.plan` spans, over
those spans. 64 on a static set; a change every 16 heights cuts it to about 16.
"""

from benchmark import churn_readers

LAYER = "entry"
UNIT = "commits/call"
SOURCE = "program_span"
MOVES = "blocksync_blocks_per_s"


def read(r):
    return churn_readers.plan_commits_per_verify(r)
