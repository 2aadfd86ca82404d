"""Readers of the per-layer metrics that separate a layer's WORK from its
waiting. They read what the program's recorder writes once a span also reads
its thread's CPU clock: `cpu_ms` on every `Span` row (the thread's time on a
core between enter and exit), `proc_cpu_ms` on a root span's (the whole
process's), `thread` on every row. Wall minus on-CPU is what the thread spent
off the core: in a span that is pure Python, waiting for the GIL (or for a lock
or the scheduler). A recorder without the readings (the parent of the PR that
added them) leaves every reader here with nothing to read: None, after one line
that says why — never 0, never a raise.

Only spans with no `await` inside are read as work (`validation.collect`,
`hub.submit`, `tpu.resolve`, `tpu.prep`, `tpu.dispatch`): in a span that awaits
the thread's CPU clock also counts whatever else the event loop ran meanwhile.
A span the window's edge cuts counts by the share of it that lies inside, its
CPU reading and its attributes alike.

The metric files that use this module take everything from it (the harness's
own tests count the metric files that name the recorder's reader, PR 25's
twenty-six).
"""

from __future__ import annotations

from benchmark import program_spans as ps

#: pure-Python spans of the verify funnel: what is off the core there is waiting
PURE_PYTHON = ("validation.collect", "hub.submit", "tpu.resolve", "tpu.prep")
#: one a unit of the window's work, each a trace of its own
ROOTS = ("blocksync.range", "light.window")
DISPATCH = "tpu.dispatch"

def carrying(r, field: str, *keys: str) -> list[tuple[dict, float]] | None:
    """(row, share of it inside the window) for the window's rows called
    `keys` that carry `field`; None where there is none."""
    rows = ps.window_rows(r.t0, r.t1)
    if rows is None:  # window_rows has said why
        return None
    mine = [d for d in ps.select(rows, *keys) if field in d]
    if not mine:
        ps._once(f"no {' / '.join(keys)} row of the window carries {field}: a "
                 "recorder without CPU readings, or no such span")
        return None
    out = []
    for d in mine:
        length = d["end"] - d["start"]
        out.append((d, ps._clip(d, r.t0, r.t1) / length if length > 0 else 1.0))
    return out


def _sum(mine, field: str) -> float:
    return sum(share * float(d[field]) for d, share in mine)


def cpu_ms_per_ksig(r, attr: str, key: str):
    """On-CPU ms of spans `key` per thousand of what their `attr` counts."""
    mine = carrying(r, "cpu_ms", key)
    if mine is None:
        return None
    n = sum(share * float((d.get("attrs") or {}).get(attr, 0)) for d, share in mine)
    return _sum(mine, "cpu_ms") / (n / 1e3) if n else None


def off_cpu_share(r):
    """% of the wall time inside the funnel's pure-Python spans that their
    threads spent off a core."""
    mine = carrying(r, "cpu_ms", *PURE_PYTHON)
    if mine is None:
        return None
    wall = _sum(mine, "duration_ms")
    return 100.0 * (1.0 - _sum(mine, "cpu_ms") / wall) if wall > 0 else None


def cores_busy(r):
    """Process CPU seconds over wall seconds of the window's root spans: how
    many cores the host really used (1.0 = GIL-bound, whatever the threads)."""
    mine = carrying(r, "proc_cpu_ms", *ROOTS)
    if mine is None:
        return None
    wall = _sum(mine, "duration_ms")
    return _sum(mine, "proc_cpu_ms") / wall if wall > 0 else None


def dispatch_cpu_ms_per_dispatch(r):
    """On-CPU ms of a `tpu.dispatch` that went to more than one device."""
    mine = carrying(r, "cpu_ms", DISPATCH)
    if mine is None:
        return None
    mine = [(d, s) for d, s in mine if int((d.get("attrs") or {}).get("devices", 1)) > 1]
    n = sum(share for _d, share in mine)
    return _sum(mine, "cpu_ms") / n if n else None
