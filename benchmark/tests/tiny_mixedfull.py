"""A throw-away root with ONE tiny cell, `tinymixedfull.blocksync`: the mixed
committee behind the hub — its own configuration, driver and metric files —
at 7 validators (4 ed25519 + 3 secp256k1; 5 signatures reach > 2/3: 2 + 3)
and a 1,200-block chain, the way `tiny_churn.py` builds its one. The reactor's
window stays the program's (`DEFAULT_WINDOW`: the driver holds a cell to it).
`tests/test_mixedfull150.py` (tier-1) and `test_mixedfull.py` here drive it."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "tinymixedfull.blocksync"
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def make_root(tmp: str, validators: int = 7, blocks: int = 1200, warmup_blocks: int = 48,
              quorum_mix: str = "2 ed25519 + 3 secp256k1") -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(base, sub))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(base, "metrics"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "mixedfull150.json")))
    cfg.update(name="tinymixedfull")
    cfg["validators"]["count"] = validators
    json.dump(cfg, open(os.path.join(base, "configs", "tinymixedfull.json"), "w"))
    cell = json.load(open(os.path.join(BENCH, "workloads", "mixedfull150.blocksync.json")))
    cell.update(name=CELL, config="tinymixedfull")
    cell["traffic"].update(blocks=blocks, warmup_blocks=warmup_blocks, quorum_mix=quorum_mix,
                           trace_seconds=0.1)
    json.dump(cell, open(os.path.join(base, "workloads", f"{CELL}.json"), "w"))
    bench["configs"] = [{"name": "tinymixedfull", "source": "test", "why": "test",
                         "reduced": ["stores", "chain_length"],
                         "file": "benchmark/configs/tinymixedfull.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tinymixedfull", "traffic": "blocksync",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "mixedfull150.blocksync" in m["workloads"] else []
    json.dump(bench, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
