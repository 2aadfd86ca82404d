"""A throw-away root with ONE tiny cell, `tinychurn.blocksync`: the changing
committee's own configuration, driver and metric files at 7 validators (5
signatures reach > 2/3), a change every 4 heights and a 400-block chain, the
way `tiny_mixed.py` builds its one. `tests/test_churn150.py` (tier-1) and
`test_churn.py` here drive it on the host route."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "tinychurn.blocksync"
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def make_root(tmp: str, validators: int = 7, blocks: int = 400, period: int = 4) -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(base, sub))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(base, "metrics"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "churn150.json")))
    cfg.update(name="tinychurn")
    cfg["validators"]["count"] = validators
    cfg["rotation"]["period"] = period
    json.dump(cfg, open(os.path.join(base, "configs", "tinychurn.json"), "w"))
    cell = json.load(open(os.path.join(BENCH, "workloads", "churn150.blocksync.json")))
    cell.update(name=CELL, config="tinychurn")
    cell["traffic"].update(blocks=blocks, warmup_blocks=48, rotation_period=period,
                           trace_seconds=0.1)
    json.dump(cell, open(os.path.join(base, "workloads", f"{CELL}.json"), "w"))
    bench["configs"] = [{"name": "tinychurn", "source": "test", "why": "test",
                         "reduced": ["stores", "chain_length"],
                         "file": "benchmark/configs/tinychurn.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tinychurn", "traffic": "blocksync",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "churn150.blocksync" in m["workloads"] else []
    json.dump(bench, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
