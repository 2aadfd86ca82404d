#!/usr/bin/env python3
"""`control.py` with one more control, for a cell whose committee changes:

    python3 benchmark/control_churn.py --workload churn150.blocksync \\
        --seeds 1,2,3 --seconds 10 --control stale_set --control-seeds 1

`stale_set` breaks the guarantee that only such a configuration states — a
commit counts only under the validator set of its own height — where it is
kept: around the reactor's `verify_commit_range` and `verify_commit_light`,
the plain reference grants every commit the program refuses ONE HEIGHT OF
GRACE: if more than 2/3 of the set the reactor gave the height BEFORE signed
it, in that set's order, it passes (a key that has left, or a power that has
changed, still counts for one more height). The rest goes on through the
program as it is, so on honest traffic its verdicts, its signature counts
and its routes are the program's own; the warm-up chain's stale-set commit
is what it lets through (the block after it is then refused for what it is,
one height late), so `correct` has to come out false
(`warmup_refusal_height_delta.stale_set`, alone). The benchmark's own runs
never run it.
"""

from __future__ import annotations

import contextlib
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import control, fixtures, harness  # noqa: E402
from benchmark import reference as ref  # noqa: E402


@contextlib.contextmanager
def stale_set():
    from tendermint_tpu.blocksync import reactor
    from tendermint_tpu.types.validation import InvalidCommitError

    given: dict = {}  # (chain ID, height) -> the set the reactor gave that height

    def stale_set_accepts(chain_id, block_id, height, commit) -> bool:
        prev = given.get((chain_id, height - 1))
        if (prev is None or commit.height != height or commit.block_id != block_id
                or commit.size() != len(prev)):
            return False
        return ref.commit_verdict(fixtures.commit_data(chain_id, commit, prev))[0]

    def make(orig):
        def graced(chain_id, entries, **kw):
            for vals, _bid, height, _commit in entries:
                given[(chain_id, height)] = vals
            done = 0
            while done < len(entries):
                try:
                    return orig(chain_id, entries[done:], **kw)
                except InvalidCommitError as e:
                    at = done + getattr(e, "failed_index", 0)
                    _vals, block_id, height, commit = entries[at]
                    if not stale_set_accepts(chain_id, block_id, height, commit):
                        e.failed_index = at
                        raise
                    done = at + 1

        return graced

    def make_one(orig):
        def graced(chain_id, vals, block_id, height, commit, **kw):
            given[(chain_id, height)] = vals
            try:
                return orig(chain_id, vals, block_id, height, commit, **kw)
            except InvalidCommitError:
                if not stale_set_accepts(chain_id, block_id, height, commit):
                    raise

        return graced

    patches = harness.Patches()
    patches.wrap(reactor, "verify_commit_range", make)
    patches.wrap(reactor, "verify_commit_light", make_one)
    try:
        yield
    finally:
        patches.undo()


control.CONTROLS["stale_set"] = stale_set


if __name__ == "__main__":
    try:
        code = control.main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
