#!/usr/bin/env python3
"""The child process `harness.FixtureChild` starts: one cell's seeded fixture,
built by the cell's own driver with the program's device switched off, each
part the driver yields written to stdout as one pickle behind its length
(`harness.write_part`). Progress goes to stderr on the parent's clock
(`--t0`: CLOCK_MONOTONIC is the machine's).

    python3 benchmark/fixture_child.py --root <dir> --workload <cell> --seed <n> --t0 <s>
"""

from __future__ import annotations

import argparse
import importlib
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", required=True)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--t0", type=float, required=True)
    args = ap.parse_args(argv)
    if not os.environ.get("TMTPU_DISABLE_TPU") or os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit("fixture_child: started by harness.FixtureChild alone (device off)")
    harness.T0 = args.t0
    _bench, cell, cfg, _layer = run.load_cell(args.root, args.workload)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    out = sys.stdout.buffer
    sys.stdout = sys.stderr  # nothing but the pickles on the pipe
    t0 = time.monotonic()
    for i, part in enumerate(driver.build(cfg, cell, args.seed)):
        built = time.monotonic() - t0
        harness.write_part(out, part)
        harness.say(f"fixture child: part {i} built {built:.1f}s after the child's start, "
                    f"on the pipe {time.monotonic() - t0 - built:.1f}s later")
    return 0


if __name__ == "__main__":
    code = main()
    sys.stderr.flush()
    # nothing left to save: no teardown of a heap that holds a 4,096-block
    # chain's builder while the measured process waits for this exit
    os._exit(code)
