#!/usr/bin/env python3
"""Sound seeds and the control, read in ONE process on the chip (set-up is
minutes, a window seconds):

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--control weak_quorum] [--control-seeds 3]

For each seed the cell is run as `run.execute` runs it and one line is
printed: the seed, `correct`, the checks that failed, the end-to-end
metric. With --control the first --control-seeds seeds are run a second
time with the control in the program's place, which has to come out as
not correct.

The control. This system states no numeric precision on its timed path
(the chip's field multiply is the int32 Pallas kernel; the f32 HIGHEST
limb GEMM loses the program's own A/B on a v5e and is not traced), so the
control breaks one guarantee the configuration states: the plain reference
stands in for verify_commit_range / verify_commit_light and accepts a
commit once MORE THAN HALF of the voting power has signed, where the
configuration says more than two thirds. On honest traffic its verdicts
are the program's own. The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import traceback
from fractions import Fraction

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import fixtures, harness  # noqa: E402
from benchmark import reference as ref  # noqa: E402

HALF = Fraction(1, 2)


@contextlib.contextmanager
def weak_quorum():
    """Put the reference, with its quorum at 1/2, in the place of the
    program's commit verification for the reactor and the light client."""
    from tendermint_tpu.blocksync import reactor
    from tendermint_tpu.light import verifier
    from tendermint_tpu.types import validation
    from tendermint_tpu.types.validation import InvalidCommitError

    def one(chain_id, vals, block_id, height, commit, **_kw):
        if commit.height != height or commit.block_id != block_id:
            raise InvalidCommitError("commit is for a different block")
        ok, _n, bad = ref.commit_verdict(fixtures.commit_data(chain_id, commit, vals), HALF)
        if not ok:
            raise InvalidCommitError(f"invalid signature at index {bad}")

    def many(chain_id, entries, **_kw):
        for i, (vals, block_id, height, commit) in enumerate(entries):
            try:
                one(chain_id, vals, block_id, height, commit)
            except InvalidCommitError as e:
                e.failed_index = i
                raise

    patches = harness.Patches()
    for owner in (reactor, verifier, validation):
        if hasattr(owner, "verify_commit_range"):
            patches.wrap(owner, "verify_commit_range", lambda _orig: many)
        if hasattr(owner, "verify_commit_light"):
            patches.wrap(owner, "verify_commit_light", lambda _orig: one)
    try:
        yield
    finally:
        patches.undo()


CONTROLS = {"weak_quorum": weak_quorum}


def main(argv=None) -> int:
    from benchmark import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", choices=sorted(CONTROLS))
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    run.place_compile_cache(args.workload)
    device = harness.attach(run.load_cell(harness.ROOT, args.workload)[1]["chips"])
    rc = 0
    plan = [(s, None) for s in seeds]
    if args.control:
        plan += [(s, args.control) for s in seeds[: args.control_seeds]]
    for seed, ctl in plan:
        with CONTROLS[ctl]() if ctl else contextlib.nullcontext():
            res = run.execute(harness.ROOT, args.workload, seed, args.seconds, False,
                              device=device)
        failed = sorted(k for k, c in res["checks"].items() if not c["ok"])
        line = {"seed": seed, "control": ctl, "correct": res["correct"], "failed_checks": failed,
                "checks": {k: c["value"] for k, c in res["checks"].items()},
                "metrics": {k: v["value"] for k, v in res["metrics"].items() if k != "setup_s"}}
        print(json.dumps(line), flush=True)
        if res["correct"] == bool(ctl):  # sound must be correct, the control must not
            rc = 1
    return rc


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except Exception:  # noqa: BLE001
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
