"""A throw-away copy of the benchmark's data files at a tiny size (10
validators, a few hundred blocks) in a temporary directory: the way a later
PR adds a cell, a configuration and a per-layer metric — new files and new
BENCHMARK.json entries, no edit to a file that is there.

The block-sync chain keeps the real cells' room rule at its own scale: the
sandbox's host route applies some hundreds of these blocks a second, the
tests' windows are 0.6 s (a 64-block range is ~0.35 s) with a 0.1 s traced
stretch, and `BLOCKS` holds several times what that consumes — a tiny run's
chain must not end inside its window any more than a real one's
(`chain_left_blocks`)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
#: the tiny block-sync chain, and the window its tests run it for
BLOCKS = 600
SECONDS = 0.6


def make_root(tmp: str, blocks: int = BLOCKS) -> str:
    """tmp/BENCHMARK.json + tmp/benchmark/{configs,workloads,metrics} with
    two tiny cells `tinylight.sequential` and `tinyfull.blocksync`, plus one
    new per-layer metric that only the throw-away cell reports."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(base, sub))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(base, "metrics"))

    def load(rel):
        return json.load(open(os.path.join(BENCH, rel)))

    def dump(rel, obj):
        json.dump(obj, open(os.path.join(base, rel), "w"))

    light_cfg = load("configs/light150.json")
    light_cfg.update(name="tinylight")
    light_cfg["validators"]["count"] = 10
    dump("configs/tinylight.json", light_cfg)
    full_cfg = load("configs/full150.json")
    full_cfg.update(name="tinyfull")
    full_cfg["validators"]["count"] = 10
    dump("configs/tinyfull.json", full_cfg)

    light = load("workloads/light150.sequential.json")
    light.update(name="tinylight.sequential", config="tinylight")
    light["traffic"].update(headers=40, warmup_headers=20, trace_seconds=0.2)
    dump("workloads/tinylight.sequential.json", light)
    sync = load("workloads/full150.blocksync.json")
    sync.update(name="tinyfull.blocksync", config="tinyfull")
    sync["traffic"].update(blocks=blocks, warmup_blocks=40, trace_seconds=0.1)
    dump("workloads/tinyfull.blocksync.json", sync)

    with open(os.path.join(base, "metrics", "verify_share.tiny.py"), "w") as f:
        f.write(
            '"""verify_share.tiny: share of the window spent inside verify."""\n'
            'LAYER = "entry"\nUNIT = "%"\nSOURCE = "program_span"\n'
            'MOVES = "light_headers_per_s"\n\n\n'
            "def read(r):\n"
            '    return 100.0 * r.spans.total("verify", r.t0, r.t1) / r.elapsed\n'
        )

    cells = {"light150.sequential": "tinylight.sequential",
             "full150.blocksync": "tinyfull.blocksync"}
    bench["configs"] = [
        {"name": "tinylight", "source": "test", "file": "benchmark/configs/tinylight.json",
         "reduced": [], "why": "test"},
        {"name": "tinyfull", "source": "test", "file": "benchmark/configs/tinyfull.json",
         "reduced": ["stores"], "why": "test"},
    ]
    bench["workloads"] = [
        {"name": "tinylight.sequential", "config": "tinylight", "traffic": "sequential",
         "chips": 1, "why": "test"},
        {"name": "tinyfull.blocksync", "config": "tinyfull", "traffic": "blocksync",
         "chips": 1, "why": "test"},
    ]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [cells[w] for w in m["workloads"] if w in cells]
    bench["per_layer"].append(
        {"name": "verify_share.tiny", "unit": "%", "better": "lower",
         "source": "program_span", "layer": "entry", "moves": "light_headers_per_s",
         "workloads": ["tinylight.sequential"]})
    json.dump(bench, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
