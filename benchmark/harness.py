"""What every driver shares: the clock, the refusal of anything but a TPU,
the program's own counters read as deltas, the spans the harness puts
around calls into each layer, and the checks that the DEVICE served a
window (the program re-verifies on the host after any device error, so a
right verdict alone says nothing about the chip).

From the program this takes only the system under test and its counters
(`crypto.backend_telemetry`, `VerifyHub.stats()`, the TPU breaker) and
function names to put spans on. Nothing here sets a TMTPU_* knob.
"""

from __future__ import annotations

import contextlib
import functools
import importlib.util
import json
import os
import sys
import threading
import time
from dataclasses import dataclass, field

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: process start, as near as Python lets us see it (run.py imports this
#: module first): setup_s runs from here to the window's opening
T0 = time.monotonic()


def say(msg: str) -> None:
    """An earlier line: progress and diagnosis go to stderr, stamped with
    seconds since process start; stdout carries the result line alone."""
    print(f"[{time.monotonic() - T0:7.1f}s] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file by path (metric readers and drivers are found by
    the names in BENCHMARK.json, and a name may hold dots)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# -- the device -------------------------------------------------------------


class NoAccelerator(RuntimeError):
    pass


def attach(want_chips: int) -> dict:
    """Bring the device up through the program's own start
    (crypto.batch._probe_tpu on its thread) and refuse anything but
    `want_chips` TPU devices. Returns as soon as the probe has named its
    platform; `wait_probe_end` waits out the rest."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: jax.devices()[0].platform={devs[0].platform!r}")
    if len(devs) < want_chips:
        raise NoAccelerator(f"need {want_chips} chip(s), jax sees {len(devs)}")
    if os.environ.get("TMTPU_DISABLE_TPU"):
        # the builder's host-route row: the program's own switch, set by
        # hand. Such a run is not a cell: its `tpu` route stays empty and
        # `correct` reads false by design
        say("TMTPU_DISABLE_TPU is set by hand: HOST ROUTE, not a cell, not a "
            "device metric")
    cb.tpu_verifier_available()  # kicks _probe_tpu on its daemon thread
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"attached: {device}; probe thread started (telemetry {bt.ACTIVE})")
    return device


def wait_available() -> None:
    """Wait for the program's own verdict on its device: attach, the
    Pallas A/B probe, the floor warm-up and the measured CPU/TPU cut-off.
    Nothing else may load the host until then: the A/B and the cut-off are
    host-clock timings of Python-dispatched calls, and a fixture build
    beside them flips their winners (PR 24: gemm for pallas, the XLA power
    chain for the fused one), which changes every kernel the process then
    traces — and with them the compile-cache keys."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb

    if os.environ.get("TMTPU_DISABLE_TPU"):
        say("host route (TMTPU_DISABLE_TPU set by hand): no probe to wait for")
        return
    if not cb.tpu_wait_available():
        raise RuntimeError(f"device probe failed: {bt.snapshot()}")
    say(f"device available (MIN_TPU_BATCH={cb.MIN_TPU_BATCH})")


def wait_probe_end() -> None:
    """Availability flips BEFORE the probe thread's 8192 warm-up ends; a
    window opened then shares the host with a compile. Wait for the
    thread itself."""
    from tendermint_tpu.crypto import backend_telemetry as bt

    for t in threading.enumerate():
        if t.name == "tpu-probe":
            t.join()
            say(f"probe thread ended; compile seconds {bt.snapshot()['compile_seconds']}")


class CompileCounters:
    """Backend compiles and persistent-cache hits/misses from jax's own
    monitoring events (as chip_smoke._CompileCounters)."""

    def __init__(self):
        import jax.monitoring as mon

        self.hits = self.misses = self.compiles = self.lowerings = 0
        self.compile_s = self.lower_s = 0.0
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            # a new program traced and lowered: the costly part of a new
            # shape even where the persistent cache then answers
            self.lowerings += 1
            self.lower_s += duration

    def snapshot(self) -> dict:
        return {"cache_hits": self.hits, "cache_misses": self.misses,
                "backend_compiles": self.compiles, "lowerings": self.lowerings,
                "backend_compile_s": round(self.compile_s, 1),
                "lowering_s": round(self.lower_s, 1)}


def memory_peak_bytes() -> int:
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


# -- the program's counters, as deltas over a window ---------------------------


def counters() -> dict:
    """One flat reading of every program counter a window is judged by."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto.verify_hub import running_hub

    out = {f"backend.{k}": float(v) for k, v in bt.BACKEND.items()}
    for route, (batches, sigs) in list(bt.ROUTES.items()):
        out[f"route.{route}.batches"] = float(batches)
        out[f"route.{route}.sigs"] = float(sigs)
    out["mesh.degrade_transitions"] = float(bt.MESH["degrade_transitions"])
    out["breaker.opens"] = float(cb.tpu_breaker().opens)
    hub = running_hub()
    if hub is not None:
        for k, v in hub.stats().items():
            out[f"hub.{k}"] = float(v)
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: after.get(k, 0.0) - before.get(k, 0.0) for k in set(after) | set(before)}


@dataclass
class Check:
    """One number compared, beside its limit. `kind` is "max" (value must
    not pass the limit) or "min" (must reach it)."""

    name: str
    value: float
    limit: float
    kind: str = "max"

    @property
    def ok(self) -> bool:
        return self.value <= self.limit if self.kind == "max" else self.value >= self.limit

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit, "kind": self.kind,
                "ok": self.ok}


def device_served_checks(d: dict) -> list[Check]:
    """What must hold in EVERY run, whatever route a single dispatch took
    (the CPU/TPU cut-off is measured per process and lands on the commit
    size): no host re-verify after a device error, breaker closed, no
    probe error, no hub error, no degrade retry — and a `tpu` route that
    carried signatures inside the window."""
    from tendermint_tpu.crypto import batch as cb
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto.tpu import verify as tpuv

    probe_faults = sum(1 for k in ("error", "scan_error") if k in tpuv.field_mul_probe)
    return [
        Check("host_reverifies", d.get("backend.fallbacks", 0.0)
              + d.get("route.cpu-fallback.sigs", 0.0), 0),
        Check("breaker_opens", d.get("breaker.opens", 0.0)
              + (0.0 if cb.tpu_breaker().state == "closed" else 1.0), 0),
        # probe errors are a property of the process's start, not of the
        # window: read whole, not as a delta
        Check("probe_errors", bt.BACKEND["probe_errors"]
              + bt.BACKEND["pallas_probe_errors"] + probe_faults
              + (0.0 if bt.ACTIVE["kind"] == "tpu" else 1.0), 0),
        Check("hub_verify_errors", d.get("hub.verify_errors", 0.0), 0),
        Check("degrade_retries", d.get("backend.degrade_retries", 0.0)
              + d.get("mesh.degrade_transitions", 0.0), 0),
        Check("tpu_route_sigs", d.get("route.tpu.sigs", 0.0), 1, "min"),
    ]


# -- spans ---------------------------------------------------------------------


@dataclass
class Spans:
    """Host-clock spans the harness records around calls into a layer,
    kept in memory. In a traced run each span is also written onto the
    profiler's clock (`jax.profiler.TraceAnnotation`), so that an idle gap
    of the device can be laid to what the host was doing."""

    annotate: bool = False
    rows: list = field(default_factory=list)  # (name, t0, t1, attrs)
    #: running seconds inside tpu.verify.resolve (per signature: summed,
    #: not a row each); filled by host_prep_spans in traced runs
    resolve_total: list = field(default_factory=lambda: [0.0])
    _open: dict = field(default_factory=dict)
    _lock: threading.Lock = field(default_factory=threading.Lock)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            ann.__enter__()
        with self._lock:
            self._open[name] = self._open.get(name, 0) + 1
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            with self._lock:
                self._open[name] -= 1
                self.rows.append((name, t0, t1, attrs))

    def open_count(self, name: str) -> int:
        with self._lock:
            return self._open.get(name, 0)

    def total(self, name: str, t_from: float = 0.0, t_to: float = float("inf")) -> float:
        """Seconds inside spans called `name`, clipped to [t_from, t_to]."""
        with self._lock:
            rows = list(self.rows)
        return sum(
            max(0.0, min(t1, t_to) - max(t0, t_from))
            for n, t0, t1, _ in rows if n == name
        )

    def select(self, name: str) -> list:
        with self._lock:
            return [r for r in self.rows if r[0] == name]


class Patches:
    """Wrappers the harness puts on the program's functions for one run,
    and takes off again."""

    def __init__(self):
        self._undo: list = []

    def wrap(self, owner, attr: str, make_wrapper) -> None:
        orig = getattr(owner, attr)
        setattr(owner, attr, make_wrapper(orig))
        self._undo.append((owner, attr, orig))

    def span(self, owner, attr: str, spans: Spans, name: str, on_call=None) -> None:
        """Put a span called `name` around owner.attr; `on_call(args,
        kwargs, result, attrs)` may note sizes on the span."""

        def make(orig):
            @functools.wraps(orig)
            def wrapped(*a, **kw):
                with spans.span(name) as attrs:
                    out = orig(*a, **kw)
                    if on_call is not None:
                        on_call(a, kw, out, attrs)
                    return out

            return wrapped

        self.wrap(owner, attr, make)

    def undo(self) -> None:
        while self._undo:
            owner, attr, orig = self._undo.pop()
            setattr(owner, attr, orig)


def host_prep_spans(patches: Patches, spans: Spans) -> None:
    """Traced runs only (a wrapper on a per-signature call costs a tenth
    of the call): spans on the host's share of a device dispatch —
    `resolve` (SHA-512 per signature, as TPUBatchVerifier.add calls it)
    and `prepare_batch_eq` (bigint z*k, packing), which also notes the
    shape each dispatch took: (signatures, bucket, group bucket)."""
    from tendermint_tpu.crypto.tpu import verify as tpuv

    def note_shape(a, kw, out, attrs):
        entries = a[0]
        attrs["n"] = sum(1 for e in entries if e is not None)
        attrs["bucket"] = int(out[1].shape[0])
        attrs["groups"] = int(out[0].shape[0])

    patches.span(tpuv, "prepare_batch_eq", spans, "host_prep", note_shape)

    total = spans.resolve_total

    def make(orig):
        @functools.wraps(orig)
        def wrapped(*a, **kw):
            t0 = time.perf_counter()
            try:
                return orig(*a, **kw)
            finally:
                total[0] += time.perf_counter() - t0

        return wrapped

    patches.wrap(tpuv, "resolve", make)


# -- the profiler ---------------------------------------------------------------


class DeviceTrace:
    """A jax.profiler trace of one stretch of the window, written under
    the checkout (`.bench_trace/`, git-ignored) and reduced by
    trace_reduce. Python tracing is off: it slows the host it measures."""

    def __init__(self, tag: str):
        self.dir = os.path.join(ROOT, ".bench_trace", tag)
        self.t_start = self.t_stop = 0.0
        self.running = False

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()
        self.running = True

    def stop(self) -> None:
        import jax

        if not self.running:
            return
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.running = False

    def path(self) -> str | None:
        import glob

        found = sorted(glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                              "*.xplane.pb")))
        return found[-1] if found else None
