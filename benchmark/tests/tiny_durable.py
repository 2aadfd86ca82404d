"""A throw-away root with ONE tiny cell, `tinydurable.blocksync`: the node on
disk — its own configuration, driver and metric files — at 10 validators and
a 1,200-block chain, the way `tiny_mixedfull.py` builds its one. The data
directory is the driver's own (`<checkout>/.bench_data/`, git-ignored).
`tests/test_durable150.py` (tier-1) and `test_durable.py` here drive it."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "tinydurable.blocksync"
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def make_root(tmp: str, validators: int = 10, blocks: int = 1200, warmup_blocks: int = 48) -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(base, sub))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(base, "metrics"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "durable150.json")))
    cfg.update(name="tinydurable")
    cfg["validators"]["count"] = validators
    json.dump(cfg, open(os.path.join(base, "configs", "tinydurable.json"), "w"))
    cell = json.load(open(os.path.join(BENCH, "workloads", "durable150.blocksync.json")))
    cell.update(name=CELL, config="tinydurable")
    cell["traffic"].update(blocks=blocks, warmup_blocks=warmup_blocks, trace_seconds=0.1)
    json.dump(cell, open(os.path.join(base, "workloads", f"{CELL}.json"), "w"))
    bench["configs"] = [{"name": "tinydurable", "source": "test", "why": "test",
                         "reduced": cfg["reduced"],
                         "file": "benchmark/configs/tinydurable.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tinydurable", "traffic": "blocksync",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "durable150.blocksync" in m["workloads"] else []
    json.dump(bench, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
