"""A throw-away root with ONE tiny cell, `tinylight.mesh4`: the four-chip
cell's own configuration, driver and metric files at 20 validators and 8
headers (7 x 14 = 98 range signatures: one 128-row dispatch), the way
`tiny.py` builds its two. `tests/test_mesh4.py` (tier-1) drives it on four
virtual devices; `test_mesh4.py` here drives it on the host route."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "tinylight.mesh4"
CPU_4 = {"platform": "cpu", "kind": "cpu", "count": 4}


def make_root(tmp: str) -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(base, sub))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(base, "metrics"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "light150x4.json")))
    cfg.update(name="tinylight4")
    cfg["validators"]["count"] = 20
    json.dump(cfg, open(os.path.join(base, "configs", "tinylight4.json"), "w"))
    cell = json.load(open(os.path.join(BENCH, "workloads", "light150.mesh4.json")))
    cell.update(name=CELL, config="tinylight4")
    cell["traffic"].update(headers=8, warmup_headers=8, trace_seconds=0.1)
    json.dump(cell, open(os.path.join(base, "workloads", f"{CELL}.json"), "w"))
    bench["configs"] = [{"name": "tinylight4", "source": "test", "reduced": [], "why": "test",
                         "file": "benchmark/configs/tinylight4.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tinylight4", "traffic": "sequential",
                           "chips": 4, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "light150.mesh4" in m["workloads"] else []
    json.dump(bench, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
