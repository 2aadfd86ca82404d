"""A throw-away root with ONE tiny cell, `tinymixed.sequential`: the mixed
committee's own configuration, driver and metric files at 20 validators
(10 ed25519 + 10 secp256k1; 14 signatures reach > 2/3) and 8 headers, the
way `tiny_mesh.py` builds its one. `tests/test_mixed150.py` (tier-1) drives
it on the device route of the suite's CPU devices; `test_mixed.py` here
drives it on the host route."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

CELL = "tinymixed.sequential"
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def make_root(tmp: str, validators: int = 20, headers: int = 8) -> str:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    base = os.path.join(tmp, "benchmark")
    for sub in ("configs", "workloads"):
        os.makedirs(os.path.join(base, sub))
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(base, "metrics"))
    cfg = json.load(open(os.path.join(BENCH, "configs", "mixed150.json")))
    cfg.update(name="tinymixed")
    cfg["validators"]["count"] = validators
    json.dump(cfg, open(os.path.join(base, "configs", "tinymixed.json"), "w"))
    cell = json.load(open(os.path.join(BENCH, "workloads", "mixed150.sequential.json")))
    cell.update(name=CELL, config="tinymixed")
    cell["traffic"].update(headers=headers, warmup_headers=headers, trace_seconds=0.1)
    json.dump(cell, open(os.path.join(base, "workloads", f"{CELL}.json"), "w"))
    bench["configs"] = [{"name": "tinymixed", "source": "test", "reduced": [], "why": "test",
                         "file": "benchmark/configs/tinymixed.json"}]
    bench["workloads"] = [{"name": CELL, "config": "tinymixed", "traffic": "sequential",
                           "chips": 1, "why": "test"}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [CELL] if "mixed150.sequential" in m["workloads"] else []
    json.dump(bench, open(os.path.join(tmp, "BENCHMARK.json"), "w"))
    return tmp
