"""`tests/tiny.py` (PR 24) maps every cell a metric lists through a table of
the two cells it knew, so a cell added since is a KeyError there, and only
a `benchmark` PR may edit that file. Until one does, the throw-away roots
of these tests are built from a copy of BENCHMARK.json in which each
metric lists only the cells that table knows; everything else of the file
is as it stands (PERF.md §7 asks for the one-line repair in tiny.py)."""

import json
import os

import pytest

KNOWN_TO_TINY = {"light150.sequential", "full150.blocksync"}


@pytest.fixture(autouse=True, scope="session")
def tiny_reads_the_cells_it_knows(tmp_path_factory):
    from benchmark.tests import tiny

    with open(os.path.join(tiny.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w in KNOWN_TO_TINY]
    copy = tmp_path_factory.mktemp("benchmark_json")
    with open(os.path.join(str(copy), "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    was, tiny.ROOT = tiny.ROOT, str(copy)
    yield
    tiny.ROOT = was
