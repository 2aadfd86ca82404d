"""BENCHMARK.json against the contract's lexical rules, and against the
files it names."""

import json
import os
import re

import pytest

from benchmark import harness

ROOT = harness.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _metrics():
    return BENCH["end_to_end"] + BENCH["per_layer"]


def test_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    assert 1 <= len(BENCH["workloads"]) <= 24 and 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16 and 1 <= len(BENCH["per_layer"]) <= 128


@pytest.mark.parametrize("m", _metrics(), ids=lambda m: m["name"])
def test_metric_entry(m):
    assert NAME.match(m["name"]), m["name"]
    assert UNIT.match(m["unit"]), m["unit"]
    assert m["better"] in ("lower", "higher")
    assert m["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if "bound" in m:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    else:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]


def test_names_are_unique_and_lexical():
    for group in (BENCH["configs"], BENCH["workloads"], _metrics()):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
    for c in BENCH["configs"]:
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16


def test_setup_s_and_coverage():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in BENCH["workloads"]:
        mine = [m for m in BENCH["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert len(mine) >= 2, w["name"]
        layer = [m for m in BENCH["per_layer"] if w["name"] in m.get("workloads", [w["name"]])]
        assert layer, w["name"]
        for m in layer:  # a per-layer metric moves a metric its cells report
            assert m["moves"] in {x["name"] for x in mine}


def test_every_named_file_exists_and_agrees():
    base = BENCH["paths"][0]
    for c in BENCH["configs"]:
        cfg = harness.load_json(os.path.join(ROOT, c["file"]))
        assert c["file"].startswith(base + "/")
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert cfg["guarantees"]
    for w in BENCH["workloads"]:
        cell = harness.load_json(os.path.join(ROOT, base, "workloads", f"{w['name']}.json"))
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert os.path.exists(os.path.join(ROOT, base, "drivers", f"{cell['driver']}.py"))
    for m in BENCH["per_layer"]:
        mod = harness.load_module(
            os.path.join(ROOT, base, "metrics", f"{m['name']}.py"), "m_" + m["name"].replace(".", "_"))
        assert (mod.LAYER, mod.UNIT, mod.SOURCE, mod.MOVES) == (
            m["layer"], m["unit"], m["source"], m["moves"])


def test_files_under_paths_are_named_lexically():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]
                                  if w["traffic"] == "blocksync"])
def test_a_block_sync_chain_outlasts_its_window_up_to_160_blocks_a_second(cell):
    """A traced run whose chain ends raises `blocksync.ChainEnded` and gives no
    result: a chain too short for a gain a later PR brings is a refused PR.
    The window and the traced stretch that follows it use the chain up only
    above (blocks - window) / (run_seconds + trace_seconds) blocks/s; the
    fastest block-sync cell reads about half of 160 (PERF.md §5)."""
    p = harness.load_json(
        os.path.join(ROOT, BENCH["paths"][0], "workloads", f"{cell}.json"))["traffic"]
    ceiling = (p["blocks"] - p["window"]) / (BENCH["run_seconds"] + p["trace_seconds"])
    assert ceiling >= 160, f"{cell}: the chain ends at {ceiling:.1f} blocks/s"
