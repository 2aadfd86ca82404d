#!/usr/bin/env python3
"""tracectl — summarize flight-recorder dumps into per-stage latency
tables.

Input: the JSON served by a node's ``/debug/traces`` endpoint (or a
flight auto-dump file written on wedge/breaker-trip) — either shape is
accepted: ``{"spans": [...]}`` wrappers or a bare span list.

    python scripts/tracectl.py dump.json            # per-stage table
    curl -s localhost:26657/debug/traces | python scripts/tracectl.py -
    python scripts/tracectl.py dump.json --trace 42 # one trace, as a tree
    python scripts/tracectl.py dump.json --subsystem hub
    python scripts/tracectl.py dump.json --per-device  # mesh shard table
    python scripts/tracectl.py dump.json --per-thread  # rows, wall, on-CPU by thread

The per-stage table answers the ROADMAP question ("where did this vote
spend its time?") in aggregate: count, p50, p90, p99, max, total and
SELF time (the stage minus what its children — by ``parent_id`` — cover)
per (subsystem, name) stage. ``--trace`` prints one end-to-end trace as
a tree by ``parent_id``, children under their parent in start order,
each with its self time — and, where the rows carry them (a recorder
that reads its thread's CPU clock), its on-CPU time and its thread: a
block-sync range reads
``blocksync.range > build, verify > validation.* > hub.* > batch.route >
tpu.*``, then one ``blocksync.apply > state.*`` per block. Dumps from
before span ids existed fall back to start order. Rows that carry
``cpu_ms`` add a ``cpums`` column to the table (wall minus on-CPU is what
the stage's threads spent waiting: for the GIL, the device, a future) and
a per-thread summary under it: rows, wall and on-CPU ms of each thread's
outermost spans. Dumps without the keys render as before.

The same spans appear in any ``jax.profiler`` trace taken while the node
runs (``jax.profiler.start_trace`` / the profiler server): events named
``tm.<subsystem>.<name>`` on the host planes (``/host:CPU``, one line per
thread), beside the device's operations on the profiler's clock.
"""

from __future__ import annotations

import argparse
import json
import sys


def load_spans(path: str) -> list[dict]:
    if path == "-":
        data = json.load(sys.stdin)
    else:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    if isinstance(data, dict):
        data = data.get("spans", [])
    if not isinstance(data, list):
        raise ValueError("expected a span list or a {'spans': [...]} object")
    return data


def _pct(sorted_vals: list[float], q: float) -> float:
    """Nearest-rank percentile on a pre-sorted list."""
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


def _union_ms(intervals: list[tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if a > end:
            total, end = total + (b - a), b
        elif b > end:
            total, end = total + (b - end), b
    return total


def self_times(spans: list[dict]) -> dict[int, float]:
    """span_id -> ms of the span that none of its children (by
    ``parent_id``) cover: its duration minus the union of theirs, clipped
    to it. Spans without ids (older dumps) are all self."""
    kids: dict[int, list[tuple[float, float]]] = {}
    for s in spans:
        if s.get("parent_id"):
            a = s.get("start_s", 0.0) * 1e3
            kids.setdefault(s["parent_id"], []).append((a, a + s.get("duration_ms", 0.0)))
    out = {}
    for s in spans:
        sid = s.get("span_id")
        if not sid:
            continue
        a = s.get("start_s", 0.0) * 1e3
        b = a + s.get("duration_ms", 0.0)
        covered = _union_ms(
            [(max(a, x), min(b, y)) for x, y in kids.get(sid, ()) if y > a and x < b]
        )
        out[sid] = max(0.0, (b - a) - covered)
    return out


def summarize(spans: list[dict]) -> str:
    """Per-stage latency table (the shape the acceptance run reads)."""
    stages: dict[str, list[float]] = {}
    selfs: dict[str, float] = {}
    cpus: dict[str, float] = {}
    own = self_times(spans)
    for s in spans:
        key = f"{s.get('subsystem', '?')}.{s.get('name', '?')}"
        dur = float(s.get("duration_ms", 0.0))
        stages.setdefault(key, []).append(dur)
        selfs[key] = selfs.get(key, 0.0) + own.get(s.get("span_id"), dur)
        if "cpu_ms" in s:
            cpus[key] = cpus.get(key, 0.0) + float(s["cpu_ms"])
    if not stages:
        return "no spans"
    rows = []
    for key, vals in stages.items():
        vals.sort()
        rows.append(
            (
                key,
                len(vals),
                _pct(vals, 0.50),
                _pct(vals, 0.90),
                _pct(vals, 0.99),
                vals[-1],
                sum(vals),
                selfs[key],
            )
        )
    rows.sort(key=lambda r: -r[6])  # biggest total time first
    header = (
        f"{'stage':<28} {'count':>7} {'p50ms':>9} {'p90ms':>9} {'p99ms':>9} "
        f"{'maxms':>9} {'totalms':>10} {'selfms':>10}"
    )
    if cpus:  # on-CPU ms of the stage's spans that carry a reading
        header += f" {'cpums':>10}"
    lines = [header, "-" * len(header)]
    for key, n, p50, p90, p99, mx, total, own_ms in rows:
        line = (
            f"{key:<28} {n:>7} {p50:>9.3f} {p90:>9.3f} {p99:>9.3f} "
            f"{mx:>9.3f} {total:>10.2f} {own_ms:>10.2f}"
        )
        if cpus:
            line += f" {cpus[key]:>10.2f}" if key in cpus else f" {'-':>10}"
        lines.append(line)
    if cpus:
        lines += ["", per_thread(spans)]
    return "\n".join(lines)


def thread_totals(spans: list[dict]) -> dict:
    """thread -> [rows, wall ms, on-CPU ms]: every row the thread
    recorded, and the wall and on-CPU time of its OUTERMOST spans that
    carry ``cpu_ms`` (a nested span's time is inside its parent's; where
    two spans of one thread overlap partly — tasks of one event loop —
    the later one counts by the share that sticks out)."""
    out: dict = {}
    by_thread: dict = {}
    for s in spans:
        if "thread" not in s:
            continue
        out.setdefault(s["thread"], [0, 0.0, 0.0])[0] += 1
        if "cpu_ms" in s:
            by_thread.setdefault(s["thread"], []).append(s)
    for thread, mine in by_thread.items():
        mine.sort(key=lambda s: (s.get("start_s", 0.0), -s.get("duration_ms", 0.0)))
        end = float("-inf")
        for s in mine:
            a = s.get("start_s", 0.0) * 1e3
            dur = float(s.get("duration_ms", 0.0))
            b = a + dur
            if b <= end:
                continue
            out_ms = b - max(a, end)
            out[thread][1] += out_ms
            out[thread][2] += float(s["cpu_ms"]) * (out_ms / dur if dur > 0 else 1.0)
            end = b
    return out


def per_thread(spans: list[dict]) -> str:
    """Per-thread summary: which thread recorded how many rows, and how
    long its spans had it on a core against how long they were open."""
    totals = thread_totals(spans)
    if not totals:
        return "no row carries a thread (a dump from before the recorder kept it)"
    header = f"{'thread':<28} {'rows':>7} {'wallms':>11} {'cpums':>11} {'on-cpu':>7}"
    lines = [header, "-" * len(header)]
    for thread, (n, wall, cpu) in sorted(totals.items(), key=lambda kv: -kv[1][2]):
        share = f"{cpu / wall:>6.1%}" if wall > 0 else f"{'-':>6}"
        lines.append(f"{thread!s:<28} {n:>7} {wall:>11.2f} {cpu:>11.2f} {share:>7}")
    return "\n".join(lines)


def per_device(spans: list[dict]) -> str:
    """Per-device shard-occupancy table from the hub.dispatch spans'
    mesh attrs (devices=[ids], shards=[real-signature counts]): how
    evenly the mesh is fed, straight from a flight dump."""
    dispatches: dict = {}
    sigs: dict = {}
    total_sigs = 0
    for s in spans:
        if s.get("subsystem") != "hub" or s.get("name") != "dispatch":
            continue
        attrs = s.get("attrs") or {}
        devices, shards = attrs.get("devices"), attrs.get("shards")
        if not devices or shards is None:
            continue
        for dev, n in zip(devices, shards):
            dispatches[dev] = dispatches.get(dev, 0) + 1
            sigs[dev] = sigs.get(dev, 0) + int(n)
            total_sigs += int(n)
    if not dispatches:
        return "no sharded hub.dispatch spans (single-device or CPU route)"
    header = f"{'device':>8} {'dispatches':>11} {'sigs':>10} {'share':>7} {'sigs/dispatch':>14}"
    lines = [header, "-" * len(header)]
    for dev in sorted(dispatches):
        n, total = dispatches[dev], sigs[dev]
        share = total / total_sigs if total_sigs else 0.0
        lines.append(
            f"{dev!s:>8} {n:>11} {total:>10} {share:>6.1%} {total / n:>14.1f}"
        )
    return "\n".join(lines)


def render_trace(spans: list[dict], trace_id: int) -> str:
    """One trace as a tree by ``parent_id`` — a message's (or a range's)
    life, top down, children in start order under their parent, each
    line with the span's duration and its self time."""
    mine = [s for s in spans if s.get("trace_id") == trace_id]
    if not mine:
        return f"no spans for trace {trace_id}"
    mine.sort(key=lambda s: (s.get("start_s", 0.0), -s.get("duration_ms", 0.0)))
    t0 = mine[0].get("start_s", 0.0)
    own = self_times(mine)
    ids = {s.get("span_id") for s in mine if s.get("span_id")}
    children: dict[int, list[dict]] = {}
    roots = []
    for s in mine:
        parent = s.get("parent_id")
        if parent and parent in ids:
            children.setdefault(parent, []).append(s)
        else:
            roots.append(s)  # the root, or a row whose parent the ring dropped
    lines = [f"trace {trace_id} ({len(mine)} spans):"]

    def walk(s: dict, depth: int) -> None:
        at = (s.get("start_s", 0.0) - t0) * 1e3
        attrs = s.get("attrs") or {}
        extra = " ".join(f"{k}={v}" for k, v in attrs.items())
        dur = s.get("duration_ms", 0.0)
        label = "  " * depth + f"{s.get('subsystem', '?')}.{s.get('name', '?')}"
        cpu = f"cpu {s['cpu_ms']:9.3f}ms " if "cpu_ms" in s else ""
        if "thread" in s:
            extra = f"[{s['thread']}] {extra}"
        lines.append(
            f"  +{at:9.3f}ms {label:<34} {dur:9.3f}ms "
            f"self {own.get(s.get('span_id'), dur):9.3f}ms {cpu} {extra}"
        )
        for c in children.get(s.get("span_id"), ()):
            walk(c, depth + 1)

    for s in roots:
        walk(s, 0)
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("dump", help="dump file path, or - for stdin")
    ap.add_argument("--subsystem", help="only this subsystem's spans")
    ap.add_argument("--trace", type=int, help="print one trace as a tree, with self times")
    ap.add_argument(
        "--per-thread",
        action="store_true",
        help="per-thread rows, wall and on-CPU ms (rows that carry thread / cpu_ms)",
    )
    ap.add_argument(
        "--per-device",
        action="store_true",
        help="per-device mesh shard occupancy from hub.dispatch spans",
    )
    args = ap.parse_args(argv)
    try:
        spans = load_spans(args.dump)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"tracectl: cannot read {args.dump}: {e}", file=sys.stderr)
        return 2
    if args.subsystem:
        spans = [s for s in spans if s.get("subsystem") == args.subsystem]
    if args.trace is not None:
        print(render_trace(spans, args.trace))
    elif args.per_thread:
        print(per_thread(spans))
    elif args.per_device:
        print(per_device(spans))
    else:
        print(summarize(spans))
    return 0


if __name__ == "__main__":
    sys.exit(main())
