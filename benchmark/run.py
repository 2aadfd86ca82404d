#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json, in a new process, on the chip.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by the names in BENCHMARK.json:
`configs[].file` (the deployment), `benchmark/workloads/<cell>.json` (the
traffic and the driver), `benchmark/drivers/<driver>.py` (fixture, warm-up,
window and comparison for one entry point) and `benchmark/metrics/<metric>.py`
(one per-layer reader each). Adding a cell, a configuration or a per-layer
metric adds files and entries, and edits none.

A block-sync cell's seeded fixture is built by a child process started first
of all (`harness.FixtureChild`: the program with its device off, on another
core); a light cell's in this process, beside the probe thread's last
warm-up (`harness.FixtureHere`). Set-up (attach, the program's own device
probe to the END of its thread, the cell's own warm-up) is timed as
`setup_s` = the window's opening - the process's start - `fixture_wait_s`,
the seconds this process was blocked on that child with nothing of its own
left to run. Then the window runs for --seconds; then the device's peak
memory is read, the plain reference checks what the window produced, and
ONE JSON object is printed as the last line of stdout, `fixture_wait_s` in
it (and, from a block-sync driver, `chain_left_blocks`). Progress and every
number compared go to stderr. No TPU, too few chips, or any exception: traceback, non-zero exit,
no result line.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402  (first: it stamps the process start)
from benchmark import readers, trace_reduce  # noqa: E402
from benchmark.harness import say  # noqa: E402


def load_cell(root: str, workload: str) -> tuple[dict, dict, dict, list[dict]]:
    """(benchmark, cell file, configuration file, this cell's per-layer
    entries) for the cell called `workload`, all found by name."""
    bench = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json")
    base = bench["paths"][0]
    cell = harness.load_json(os.path.join(root, base, "workloads", f"{workload}.json"))
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cfg = harness.load_json(os.path.join(root, config["file"]))
    for key in ("config", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(f"{workload}: {key} differs between BENCHMARK.json and the cell file")
    layer = [m for m in bench["per_layer"] if workload in m.get("workloads", [workload])]
    return bench, cell, cfg, layer


def end_to_end_for(bench: dict, workload: str) -> list[dict]:
    return [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])]


def place_compile_cache(workload: str) -> str:
    """Give the program its compile cache: one directory per cell, at a
    fixed path — under JAX_COMPILATION_CACHE_DIR where the machine sets
    it, else under the program's own `<checkout>/.jax_cache`. Per cell,
    because the chip machines cap a cache directory's size
    (JAX_COMPILATION_CACHE_MAX_SIZE, 192 MiB): two cells' programs in one
    directory pass the cap, entries are evicted, and a run that should
    find every program compiled spends minutes compiling. Must run before
    jax is imported (jax reads the variable at import, and the program
    sets no directory of its own where it is set)."""
    base = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        harness.ROOT, ".jax_cache")
    path = os.path.join(base, workload)
    os.makedirs(path, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path


def execute(root: str, workload: str, seed: int, seconds: float, traced: bool,
            device: dict | None = None) -> dict:
    """One run of one cell; returns the result object. `root` holds
    BENCHMARK.json and the cell's data files. `device` is given only by
    tests, which have no chip: everything else of a run is driven as is."""
    bench, cell, cfg, layer_entries = load_cell(root, workload)
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    say(f"cell {workload} (config {cell['config']}, driver {cell['driver']}), "
        f"seed {seed}, {seconds:g}s, trace {int(traced)}")
    builder = harness.fixture_builder(driver, root, workload, cfg, cell, seed)
    spans = harness.Spans(annotate=traced)
    patches = harness.Patches()
    fx = None
    try:
        compiles = harness.CompileCounters()
        if device is None:
            device = harness.attach(cell["chips"])
        from tendermint_tpu.crypto.tpu import verify as tpuv

        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or tpuv.COMPILE_CACHE_DIR
        say(f"compile cache: {cache} "
            f"(cap {os.environ.get('JAX_COMPILATION_CACHE_MAX_SIZE', 'none')})")

        # the fixture is host work, beside the probe thread's 8192 warm-up (a
        # plain compile) once the program's own timed measurement is over:
        # built here, or what a child has handed over by now read in — never
        # waited for while that thread still runs (the wait would hide under
        # it and be taken out of setup_s all the same)
        harness.wait_available()
        fx = builder.take(block=False)
        harness.wait_probe_end()
        if fx is None:
            fx = builder.take()
        say(f"field_mul_probe {json.dumps(tpuv.field_mul_probe)}; "
            f"compiles {compiles.snapshot()}")

        driver.install(patches, spans, traced)
        warmed = driver.warmup(fx, cfg, cell, spans)
        say(f"warmed shapes: {warmed}; compiles {compiles.snapshot()}")

        builder.finish(fx)
        trace = harness.DeviceTrace(workload) if traced else None
        resolve_total = spans.resolve_total
        before, compiles_before, resolve_before = (
            harness.counters(), compiles.snapshot(), resolve_total[0])
        close: dict = {}

        def on_close() -> None:
            close.update(counters=harness.counters(), compiles=compiles.snapshot(),
                         resolve=resolve_total[0])

        fixture_wait_s = builder.waited_s
        setup_s = time.monotonic() - harness.T0 - fixture_wait_s
        say(f"set-up took {setup_s:.1f}s beside {fixture_wait_s:.3f}s blocked on the "
            f"fixture child (fixture_wait_s, not in setup_s); window opens")
        w = driver.window(fx, cfg, cell, seconds, patches, trace, spans, on_close)
        final = harness.counters()
    finally:
        patches.undo()
        if fx is not None:
            driver.release(fx)
        builder.close()
    # the window's own readings stop at its close; the comparison reads the
    # counters once everything in flight has landed (and, in a traced run,
    # the stretch that followed)
    d = harness.delta(close["counters"], before)
    d_final = harness.delta(final, before)
    compiles_after = close["compiles"]
    inline = max(compiles_after[k] - compiles_before[k]
                 for k in ("backend_compiles", "lowerings"))
    inline = max(inline, (compiles_after["cache_hits"] + compiles_after["cache_misses"])
                 - (compiles_before["cache_hits"] + compiles_before["cache_misses"]))
    device = dict(device, memory_peak_bytes=harness.memory_peak_bytes())
    say(f"window counters: {json.dumps({k: v for k, v in sorted(d.items()) if v})}")
    say(f"inline compiles {inline}; device peak {device['memory_peak_bytes']} bytes")

    reduced = None
    if trace is not None:
        path = trace.path()
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        rows = trace_reduce.load(path)
        say(f"trace {os.path.getsize(path)} bytes: {trace_reduce.describe(rows)}")
        reduced = trace_reduce.reduce(rows, trace.t_stop - trace.t_start)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        say(f"trace: busy {reduced['busy_s']:.4f}s of {reduced['window_s']:.4f}s on "
            f"{reduced['device_planes']}, {reduced['device_events']} device events, "
            f"programs {json.dumps(reduced['programs'])}")

    t_ref = time.perf_counter()
    checks, attempted, failed = driver.compare(fx, w, d_final, spans)
    say(f"reference comparison took {time.perf_counter() - t_ref:.1f}s")

    metrics: dict = {}
    if traced:
        r = readers.Readings(
            units=w.units, elapsed=w.elapsed, t0=w.t0, t1=w.t1, spans=spans, counters=d,
            inline_compiles=inline, resolve_s=close["resolve"] - resolve_before,
            device_kind=device["kind"], trace=reduced,
            stretch=(trace.t_start, trace.t_stop),
        )
        base = bench["paths"][0]
        for m in layer_entries:
            mod = harness.load_module(
                os.path.join(root, base, "metrics", f"{m['name']}.py"),
                f"benchmark_metric_{m['name'].replace('.', '_')}")
            for key, want in (("UNIT", m["unit"]), ("LAYER", m["layer"]),
                              ("SOURCE", m["source"]), ("MOVES", m["moves"])):
                if getattr(mod, key) != want:
                    raise RuntimeError(f"metric {m['name']}: {key} {getattr(mod, key)!r} "
                                       f"!= BENCHMARK.json {want!r}")
            value = mod.read(r)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(w.metrics, setup_s=setup_s)
        for m in end_to_end_for(bench, workload):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    correct = all(c.ok for c in checks)
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    # the harness's own numbers beside the contract's keys: the seconds taken
    # out of setup_s, and whatever the driver says of its window's room
    beside = {"fixture_wait_s": fixture_wait_s, **getattr(w, "report", {})}
    result.update(beside)
    for name, value in beside.items():
        say(f"{name} = {value}")
    result["checks"] = {c.name: c.as_json() for c in checks}
    for name, m in metrics.items():
        say(f"metric {name} = {m['value']} {m['unit']}")
    # every number compared, beside its limit: the last lines on stderr
    for c in checks:
        say(f"check {c.name}: {c.value} ({'<=' if c.kind == 'max' else '>='} "
            f"{c.limit}) {'ok' if c.ok else 'FAILED'}")
    say(f"correct={correct} attempted={attempted} failed={failed}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    load_cell(harness.ROOT, args.workload)  # an unknown cell stops here
    place_compile_cache(args.workload)
    result = execute(harness.ROOT, args.workload, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        rc = main()
    except SystemExit as e:
        rc = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code:
            print(e.code, file=sys.stderr)
    except Exception:  # noqa: BLE001 — any failure: traceback, non-zero, no result
        traceback.print_exc()
        rc = 1
    sys.stdout.flush()
    sys.stderr.flush()
    # the probe and hub daemon threads may still hold XLA: leave without
    # running interpreter teardown under them
    os._exit(rc)
