"""The arithmetic the per-layer metric readers share. Each file under
benchmark/metrics/ is one reader: it names its layer, unit, source and
the end-to-end metric it should move, and calls one function here with
the window's readings. A reader that finds nothing to read returns None,
and the harness leaves that metric out of the line.
"""

from __future__ import annotations

import os
from dataclasses import dataclass

from benchmark import harness, ops
from benchmark import trace_reduce as tr


@dataclass
class Readings:
    """What one window left behind for the readers."""

    units: int  # blocks applied / headers verified
    elapsed: float
    t0: float  # host clock (perf_counter) of the window
    t1: float
    spans: harness.Spans
    counters: dict  # the program's counters, as deltas over the window
    inline_compiles: int
    resolve_s: float  # seconds inside tpu.verify.resolve over the window
    device_kind: str
    trace: dict | None = None  # trace_reduce.reduce(), traced runs only
    stretch: tuple = (0.0, 0.0)  # host clock of the traced stretch


def peak_flops(device_kind: str) -> float:
    peaks = harness.load_json(os.path.join(harness.HERE, "peaks.json"))
    if device_kind not in peaks:
        raise KeyError(f"no peaks on record for device kind {device_kind!r}")
    return float(peaks[device_kind]["bf16_flops_per_s"])


def _dispatches(r: Readings, t_from: float, t_to: float) -> list[dict]:
    """Shapes of the device dispatches prepared in [t_from, t_to]."""
    return [row[3] for row in r.spans.select("host_prep") if t_from <= row[1] <= t_to]


def verify_ms_per_unit(r: Readings):
    if not r.units:
        return None
    return 1e3 * r.spans.total("verify", r.t0, r.t1) / r.units


def rest_ms_per_unit(r: Readings):
    """The window outside verify_commit_range: part-set rebuild, hashing,
    ApplyBlock, the stores, fetching."""
    if not r.units:
        return None
    return 1e3 * (r.elapsed - r.spans.total("verify", r.t0, r.t1)) / r.units


def hub_sigs_per_dispatch(r: Readings):
    n = r.counters.get("hub.dispatches", 0.0)
    return r.counters.get("hub.dispatched_sigs", 0.0) / n if n else None


def device_route_share(r: Readings):
    routed = sum(v for k, v in r.counters.items()
                 if k.startswith("route.") and k.endswith(".sigs"))
    return 100.0 * r.counters.get("route.tpu.sigs", 0.0) / routed if routed else None


def host_prep_ms_per_ksig(r: Readings):
    rows = _dispatches(r, r.t0, r.t1)
    sigs = sum(a["n"] for a in rows)
    if not sigs:
        return None
    return 1e3 * (r.spans.total("host_prep", r.t0, r.t1) + r.resolve_s) / (sigs / 1e3)


def inline_compiles(r: Readings):
    return float(r.inline_compiles)


def _traced_kernel(r: Readings):
    if not r.trace:
        return None
    kernel_s = tr.kernel_seconds(r.trace["programs"])
    rows = _dispatches(r, *r.stretch)
    if kernel_s <= 0.0 or not rows:
        return None
    return kernel_s, rows


def kernel_ms_per_ksig(r: Readings):
    got = _traced_kernel(r)
    if got is None:
        return None
    kernel_s, rows = got
    return 1e3 * kernel_s / (sum(a["n"] for a in rows) / 1e3)


def kernel_roofline_share(r: Readings):
    """Operations the dispatches of the traced stretch need, over the
    kernels' device time, over the chip's peak (compute-bound by
    construction: the operands are a few hundred KB)."""
    got = _traced_kernel(r)
    if got is None:
        return None
    kernel_s, rows = got
    need = sum(ops.needed_ops(a["bucket"], a["groups"]) for a in rows)
    return 100.0 * need / kernel_s / peak_flops(r.device_kind)


def device_idle_share(r: Readings):
    if not r.trace or r.trace["window_s"] <= 0.0 or r.trace["busy_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
