"""What every driver shares: the clock, where the seeded fixture is built
(`FixtureChild`, `FixtureHere`), the refusal of anything but a TPU, the
program's own counters read as deltas, the spans the harness puts around
calls into each layer, and the checks that the DEVICE served a window (the
program re-verifies on the host after any device error, so a right verdict
alone says nothing about the chip).

`setup_s` = the window's opening - `T0` - `fixture_wait_s`. It reads the
PROGRAM's start: attach, `_probe_tpu` to the end of its thread, the cell's
own warm-up. A block-sync cell's fixture (two chains: wire bytes and tables)
is signed by a child process on another core, off this process's heap and
GIL; `fixture_wait_s` is the seconds this process spent BLOCKED on that
child with nothing of its own left to run — it asks for the first part
without blocking while the probe thread still warms its last shape, and
blocks only once that thread has ended; for the chain, and the child's
exit, only once the cell's warm-up is over — so every second of it delayed
the window's opening, and it is taken out and printed beside `setup_s` in
every result line. Reading the hand-over in is this process's own work and
stays in `setup_s`. A light cell's fixture is built in this process
(`FixtureHere`, and why): its seconds are in `setup_s`, as before PR 38, and
its `fixture_wait_s` is 0.

From the program this takes only the system under test and its counters
(`crypto.backend_telemetry`, `VerifyHub.stats()`, the TPU breaker) and
function names to put spans on. The measured process sets no TMTPU_* knob;
the fixture child runs the program with its device switched off
(`TMTPU_DISABLE_TPU`, `JAX_PLATFORMS=cpu`): it signs, it never verifies on
a chip, and a chip belongs to one process.
"""

from __future__ import annotations

import contextlib
import fcntl
import functools
import importlib.util
import json
import os
import pickle
import queue
import subprocess
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


# -- the fixture: in a child process where it crosses as bytes, else here --------
#
# A driver's `build(cfg, cell, seed)` is a generator. It yields the fixture
# as soon as the cell's warm-up can start, and — where the window's own data
# takes longer (a 4,096-block chain) — once more: a dict of the fields filled
# since (`assemble`). A driver whose fixture is BYTES and tables (block-sync:
# wire blocks, hashes, transactions) says `FIXTURE = "child"` and
# `FixtureChild` runs that generator in a child process, each yield one plain
# pickle on its stdout. Every other driver's is built by `FixtureHere`, in the
# measured process, where it stood before PR 38: a light chain is a graph of
# the program's own objects that the window walks, and a process that REVIVED
# that graph from a pickle ran the light client 2-7% slower than one that
# built it (PERF.md §6, PR 38) — a yardstick may not move what it measures.
# The two have one interface: `take(block)`, `finish(fx)`, `close()`,
# `waited_s`.


def assemble(parts):
    """The fixture from everything `parts` (a driver's `build(...)`) yields."""
    parts = iter(parts)
    fx = next(parts)
    for late in parts:
        vars(fx).update(late)
    return fx


class FixtureHere:
    """`driver.build` in THIS process, when `take` is called: its seconds are
    the measured process's own work, inside `setup_s`; nothing is waited for."""

    waited_s = 0.0

    def __init__(self, driver, cfg: dict, cell: dict, seed: int):
        self._build = lambda: assemble(driver.build(cfg, cell, seed))

    def take(self, block: bool = True):
        return self._build()

    def finish(self, fx) -> None:
        pass

    def close(self) -> None:
        pass


#: one part on the pipe: this many bytes of length, then the pickle
PART_HEADER = 8
#: the pipe as large as an unprivileged process may make it (Linux: 1 MiB)
_PIPE_BYTES = 1 << 20


def write_part(out, part) -> None:
    """One part of a fixture onto the child's stdout: pickled whole in the
    child's memory first, so the pickling waits for no reader."""
    payload = pickle.dumps(part, protocol=pickle.HIGHEST_PROTOCOL)
    out.write(len(payload).to_bytes(PART_HEADER, "big"))
    out.write(payload)
    out.flush()


class FixtureChild:
    """`driver.build` in a child process started NOW: the same seed, the same
    builders, the program's device switched off. `take()` hands over the
    fixture once the child has dumped it (`take(block=False)`: None where it
    has not yet), `finish(fx)` fills in what it built after that and waits
    for it to end; `waited_s` is how long this process was blocked in the
    two, waiting for the child's bytes or its exit (not the unpickling: that
    is work). `close()` ends a child that is still running.

    A thread that does nothing but blocking reads drains the pipe as the child
    writes: on the chip's host (gVisor) a pipe hands a 64 KiB buffer over in
    ≈ 40 ms, so a 66 MB chain read only once it is asked for would cost tens
    of seconds of blocked time (PR 38's first chip call: 21 MB, 12 s) —
    drained beside the program's start it costs none."""

    def __init__(self, root: str, workload: str, seed: int):
        env = dict(os.environ, JAX_PLATFORMS="cpu", TMTPU_DISABLE_TPU="1")
        # the cell's compile cache is for the chip's programs alone
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        self.waited_s = 0.0
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "fixture_child.py"), "--root", root,
             "--workload", workload, "--seed", str(seed), "--t0", repr(T0)],
            stdout=subprocess.PIPE, bufsize=0, env=env)
        try:
            fcntl.fcntl(self.proc.stdout.fileno(), fcntl.F_SETPIPE_SZ, _PIPE_BYTES)
        except OSError:
            pass  # the default size: slower, the same bytes
        self._parts: queue.SimpleQueue = queue.SimpleQueue()  # pickled parts, then None
        self._reader = threading.Thread(target=self._drain, name="fixture-child-pipe",
                                        daemon=True)
        self._reader.start()

    def _read(self, n: int) -> bytes | None:
        """Exactly `n` bytes of the pipe; None at its end."""
        buf = bytearray()
        while len(buf) < n:
            chunk = self.proc.stdout.read(min(n - len(buf), _PIPE_BYTES))
            if not chunk:
                return None
            buf += chunk
        return bytes(buf)

    def _drain(self) -> None:
        while (head := self._read(PART_HEADER)) is not None:
            payload = self._read(int.from_bytes(head, "big"))
            if payload is None:
                break
            self._parts.put(payload)
        self._parts.put(None)

    def _load(self):
        """The next part, unpickled; None at the child's end."""
        t0 = time.monotonic()
        payload = self._parts.get()
        self.waited_s += time.monotonic() - t0
        if payload is None:
            self._parts.put(None)  # the end stays the end for the next call
            return None
        # only bytes this program's own child wrote are unpickled
        return pickle.loads(payload)

    def take(self, block: bool = True):
        if not block and self._parts.empty():
            return None
        fx = self._load()
        if fx is None:
            raise RuntimeError(f"the fixture child ended with code {self.proc.wait()} "
                               "before it handed a fixture over (its lines are above)")
        return fx

    def finish(self, fx) -> None:
        while (late := self._load()) is not None:
            vars(fx).update(late)
        t0 = time.monotonic()
        rc = self.proc.wait()
        self.waited_s += time.monotonic() - t0
        if rc:
            raise RuntimeError(f"the fixture child ended with code {rc}")

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        self._reader.join()
        self.proc.stdout.close()


def fixture_builder(driver, root: str, workload: str, cfg: dict, cell: dict, seed: int):
    """Where this driver's fixture is built (above): started now."""
    if getattr(driver, "FIXTURE", "here") == "child":
        return FixtureChild(root, workload, seed)
    return FixtureHere(driver, cfg, cell, seed)


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
    """Wait for the program's own verdict on its device: attach, the Pallas
    self-test against known answers (no timing decides a formulation since
    PR 29, no XLA twin runs beside it since PR 36), the floor warm-up and
    the measured CPU/TPU cut-off — the one host-clock timing left
    (`_measure_cutoff`; trap 1: `correct` never rests on where it lands).
    THIS process does nothing else until then; a block-sync cell's fixture
    child signs on another core meanwhile, and `MIN_TPU_BATCH` is printed
    for every run so that a cut-off moved by that neighbour shows."""
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
