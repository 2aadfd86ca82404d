#!/usr/bin/env python3
"""`control.py` with one more control, for a cell whose committee mixes key
types:

    python3 benchmark/control_mixed.py --workload mixed150.sequential \\
        --seeds 1,2,3 --seconds 10 --control lane_answers_true --control-seeds 1

`lane_answers_true` breaks the guarantee that only such a configuration
states — every signature is verified under its own key's scheme — where it
is kept: the verifier's host lane (`crypto.batch._verify_slice`) answers
True for every secp256k1 row without verifying it. On honest traffic its
verdicts are the program's own, and the route counts are too; the warm-up
chain's flipped ECDSA row is what it lets through, so `correct` has to come
out false (`warmup_refusal_height_delta.ecdsa`). The benchmark's own runs
never run it.
"""

from __future__ import annotations

import contextlib
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import control, harness  # noqa: E402


def _answers_true(items) -> list[bool]:
    return [True] * len(items)


@contextlib.contextmanager
def lane_answers_true():
    from tendermint_tpu.crypto import batch as cb

    patches = harness.Patches()
    patches.wrap(cb, "_verify_slice", lambda _orig: _answers_true)
    try:
        yield
    finally:
        patches.undo()


control.CONTROLS["lane_answers_true"] = lane_answers_true


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
