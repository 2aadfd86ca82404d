"""Flight-recorder tracing — bounded structured spans over the verify
funnel (the instrument panel ROADMAP's perf items keep needing: BENCH
rounds lost the TPU four times out of five and the only artifact was a
stderr tail).

A *span* is one named interval inside one *trace*: ``(trace_id, parent,
subsystem, name, start, duration, attrs)``. A trace follows one message
end-to-end — gossip receive → consensus ingest stage 1 → VerifyHub
queue/pack/dispatch → device (or CPU-fallback) execution → reorder
release → state-machine apply — so "where did this vote spend its
time?" is answerable from data instead of log archaeology.

Design constraints (all load-bearing):

  * **Clock discipline.** Spans live in the injectable Clock's
    *monotonic duration domain* (`libs/clock.Clock.monotonic`) and
    never read the wall clock: tracing must not perturb the same-seed
    bit-reproducibility the chaos matrices assert, and a span duration
    must mean the same thing under a frozen `ManualClock` (whose
    monotonic domain still advances).
  * **Allocation-light, drop-on-full.** Recording appends one small
    tuple to a bounded ring (`collections.deque(maxlen=N)`); the oldest
    span falls out when the ring is full. Nothing in here awaits,
    locks, or backpressures the hot path.
  * **Off-switchable.** ``TMTPU_TRACE=0`` (or ``[trace] enabled=false``
    via `configure`) turns the layer off: `start()` returns None,
    `span()` returns one shared no-op singleton, `record()`/`emit()`
    return before touching the ring — near-zero overhead.

Cause and identity. Every ring row carries a ``span_id`` and the
``parent_id`` of the span that was open around it (counter ids, like
``trace_id``). The *current* span lives in a ``contextvars.ContextVar``:
``with span(...)`` with no ``ctx`` inherits trace id, clock and parent
from its caller — across ``await`` and across ``asyncio.to_thread``
(which copies the context); a plain thread (the hub's dispatcher and
runners) starts with none. ``span(..., root=True)`` opens a new trace.

One clock with the device trace. While an annotator is installed
(`set_annotator`; `crypto/tpu/verify.py` installs
``jax.profiler.TraceAnnotation`` once jax is loaded — this module never
imports jax) every entered `Span` also enters an annotation named
``tm.<subsystem>.<name>``. With no profiler session that is a flag
test; inside one — ``jax.profiler.start_trace`` / the profiler server,
an operator's or a benchmark's — the program's spans sit on the host
planes of the trace (``/host:CPU``, one line per thread) beside the
device's operations, on the profiler's clock. No knob: it follows the
recorder's own switch.

Two recording APIs:

  * ``with span("hub", "dispatch", attrs...) as sp:`` — context-manager
    style for code blocks. The tmtlint `span-discipline` rule enforces
    that `span()` results are ALWAYS entered via `with` (a span held in
    a variable and never closed is a leak that silently under-reports).
  * ``record(ctx, "ingest", "verify", t0, t1, attrs...)`` — explicit
    boundary timestamps for contiguous pipeline stages, so per-stage
    durations share boundaries and sum EXACTLY to the end-to-end time.

Who ran it, and how long on a core. Every ring row carries the id of
the thread that recorded it (``thread`` in a dump: the thread's name
where it is still alive at dump time, else its id — the event loop, the
hub's dispatcher and runners, the host lane's pool and every
``to_thread`` worker write this one ring). A `Span` also reads the
calling thread's CPU clock (``time.thread_time``:
CLOCK_THREAD_CPUTIME_ID) at enter and at exit: ``cpu_ms`` is the
THREAD's time on a core between the two, so ``duration_ms - cpu_ms`` is
what the thread spent off the core — waiting for the GIL in a span that
is pure Python, for the device in ``tpu.collect``, for a future in
``hub.wait``. In a synchronous span that is the span's own work; in a
span that awaits (``blocksync.verify``, ``blocksync.apply``,
``light.fetch``, the roots) it also counts whatever other task the
event loop ran on that thread meanwhile — read ``cpu_ms`` as work only
on spans with no ``await`` inside. A span closed on another thread than
it was entered on gets no ``cpu_ms``, never a wrong one; `record` /
`finish` / `emit` rows are made from boundary timestamps, not from a
measured stretch, and carry ``thread`` alone. A ``root=True`` span also
reads the PROCESS's CPU clock (``time.process_time``) →
``proc_cpu_ms``: over the span's wall time it is how many cores the host
really used (≈ 1.0 is a process that is GIL-bound however many threads
it has). The CPU clocks are always the real ones — a `ManualClock`
trace still reports true CPU — and enter nothing but the ring, so they
cannot touch same-seed reproducibility. No knob: they follow the
recorder's one switch.

The ring dumps on demand (`/debug/traces`, `scripts/tracectl.py`) and
automatically on wedge/breaker-trip via `auto_dump(reason)` (wired from
`libs/watchdog.LoopWatchdog` and the TPU breaker in `crypto/batch.py`).

Env knobs: TMTPU_TRACE=0 disables, TMTPU_TRACE_RING sizes the ring
(default 32,768 rows: a 64-block block-sync window leaves about 6k
rows and its warm-up 3k more, and a reader of a window must find all of
it; a full ring is 6-7 MB), TMTPU_TRACE_DIR points auto-dumps at a
directory.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import os
import re
import threading
from collections import deque
from threading import get_ident
from time import process_time, thread_time

from .clock import SYSTEM, Clock

logger = logging.getLogger("libs.trace")

DEFAULT_RING = 32768

#: process-wide id sources — counters, not uuid/random/time: trace and
#: span ids never enter protocol output, and a counter keeps seeded
#: paths clean for the nondeterminism analyzer
_ids = itertools.count(1)
_span_ids = itertools.count(1)

#: the innermost open Span of this context (task / to_thread worker)
_current: contextvars.ContextVar = contextvars.ContextVar("tm_trace_span", default=None)

#: name -> context manager on the profiler's clock, or None (see
#: set_annotator)
_annotator = None


def set_annotator(factory) -> None:
    """Install (or, with None, remove) the factory that puts entered
    spans on the profiler's clock: `factory("tm.<subsystem>.<name>")`
    returns a context manager. The caller owns the import of whatever
    backs it (crypto/tpu/verify.py: jax.profiler.TraceAnnotation)."""
    global _annotator
    _annotator = factory


def annotator_installed() -> bool:
    return _annotator is not None


def current():
    """The innermost open Span of the calling context, or None: what a
    bulk caller hands to another thread so its rows join this trace
    (has `.trace_id`, `.span_id`, `.clock`, as a TraceCtx does)."""
    return _current.get()


class TraceCtx:
    """Propagated handle for one end-to-end trace: the id, the clock the
    trace is timed on, the trace's own t0 (the root span's start), and a
    small `marks` dict for boundary timestamps shared across pipeline
    stages (so stage durations sum EXACTLY to the end-to-end span)."""

    __slots__ = ("trace_id", "span_id", "t0", "clock", "marks")

    def __init__(self, trace_id: int, t0: float, clock: Clock):
        self.trace_id = trace_id
        # the root span's id (`finish` records it); every `record` on
        # this ctx is its child
        self.span_id = next(_span_ids)
        self.t0 = t0
        self.clock = clock
        self.marks: dict[str, float] = {}


class Span:
    """One in-progress span (context-manager use only — see the
    span-discipline lint rule). `set(k=v)` attaches attrs mid-flight.
    While entered it is the context's current span: spans opened inside
    it (same task, or a to_thread worker) become its children."""

    __slots__ = (
        "_rec", "trace_id", "span_id", "parent_id", "subsystem", "name", "clock",
        "_t0", "attrs", "_token", "_ann", "_thread", "_cpu0", "_proc0",
    )

    def __init__(self, rec, trace_id, parent_id, subsystem, name, clock, attrs,
                 root=False):
        self._rec = rec
        self.trace_id = trace_id
        self.span_id = next(_span_ids)
        self.parent_id = parent_id
        self.subsystem = subsystem
        self.name = name
        self.clock = clock
        self._t0 = 0.0
        self.attrs = attrs
        self._token = None
        self._ann = None
        # `_thread` and `_cpu0` are set at enter. None = not a root: the
        # process's CPU clock is not read
        self._proc0 = 0.0 if root else None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        self._token = _current.set(self)
        if _annotator is not None:
            self._ann = _annotator(f"tm.{self.subsystem}.{self.name}")
            self._ann.__enter__()
        self._thread = get_ident()
        if self._proc0 is not None:
            self._proc0 = process_time()
        self._t0 = self.clock.monotonic()
        # the CPU stretch lies inside the wall stretch: cpu <= duration
        # on the system clock, whatever the two reads cost
        self._cpu0 = thread_time()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # another thread's CPU clock says nothing about this span
        cpu = (
            thread_time() - self._cpu0
            if get_ident() == self._thread else None
        )
        dur = self.clock.monotonic() - self._t0
        proc = None if self._proc0 is None else process_time() - self._proc0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        try:
            _current.reset(self._token)
        except ValueError:
            # closed in another context than it was opened in (a
            # generator resumed elsewhere): that context never saw it
            pass
        if exc_type is not None:
            self.attrs["error"] = repr(exc)
        self._rec._append(
            self.trace_id,
            self.subsystem,
            self.name,
            self._t0,
            dur,
            self.attrs or None,
            self.span_id,
            self.parent_id,
            self._thread,
            cpu,
            proc,
        )


class _NopSpan:
    """Shared do-nothing span for disabled tracing: one module-level
    instance, zero per-call allocation."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NopSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


NOP_SPAN = _NopSpan()


class FlightRecorder:
    """Bounded per-process span ring (the "flight recorder"). All nodes
    in one process share it — like the VerifyHub they also share — so a
    dump shows the whole funnel, cross-node dedup included."""

    def __init__(
        self,
        *,
        enabled: bool = True,
        ring_size: int = DEFAULT_RING,
        out_dir: str = "",
    ):
        self.enabled = enabled
        self.ring_size = max(1, ring_size)
        self.out_dir = out_dir
        # (trace_id, subsystem, name, start_s, duration_s, attrs|None,
        #  span_id, parent_id, thread, cpu_s|None, proc_cpu_s|None); rows
        #  land in the order spans END
        self._ring: deque[tuple] = deque(maxlen=self.ring_size)
        self.recorded = 0  # total appended; dropped = recorded - len(ring)
        # auto_dump records (reason + path); bounded — /debug/flight?dump=
        # is operator-reachable, and stats() returns this list in every
        # /debug response, so it must not grow without limit
        self.dumps: deque = deque(maxlen=64)
        self._dump_seq = itertools.count(1)

    # -- recording -------------------------------------------------------

    def _append(
        self, trace_id, subsystem, name, start_s, dur_s, attrs, span_id=0, parent_id=0,
        thread=0, cpu_s=None, proc_cpu_s=None,
    ) -> None:
        # deque.append with maxlen evicts the oldest atomically under the
        # GIL — safe from both the event loop and the hub's threads
        self._ring.append(
            (trace_id, subsystem, name, start_s, dur_s, attrs,
             span_id or next(_span_ids), parent_id,
             thread or get_ident(), cpu_s, proc_cpu_s)
        )
        self.recorded += 1

    def start(self, clock: Clock | None = None) -> TraceCtx | None:
        """Open a new trace at the funnel edge; None when disabled (every
        downstream record/finish call then no-ops on the None ctx)."""
        if not self.enabled:
            return None
        clock = clock or SYSTEM
        return TraceCtx(next(_ids), clock.monotonic(), clock)

    def record(
        self,
        ctx: TraceCtx | None,
        subsystem: str,
        name: str,
        start_s: float,
        end_s: float,
        **attrs,
    ) -> None:
        """Record one contiguous pipeline stage with explicit boundary
        timestamps (taken from the ctx's clock by the caller)."""
        if ctx is None or not self.enabled:
            return
        self._append(
            ctx.trace_id, subsystem, name, start_s, end_s - start_s, attrs or None,
            0, ctx.span_id,
        )

    def finish(self, ctx: TraceCtx | None, subsystem: str, name: str, **attrs) -> None:
        """Close a trace: records the root span [ctx.t0, now]."""
        if ctx is None or not self.enabled:
            return
        now = ctx.clock.monotonic()
        self._append(
            ctx.trace_id, subsystem, name, ctx.t0, now - ctx.t0, attrs or None,
            ctx.span_id, 0,
        )

    def span(
        self,
        subsystem: str,
        name: str,
        *,
        ctx=None,
        clock: Clock | None = None,
        root: bool = False,
        **attrs,
    ) -> Span | _NopSpan:
        """Context-manager span for a code block. With a `ctx` (a
        TraceCtx, or another context's Span from `current()`) the span
        joins that trace as its child, on its clock. Without one it
        inherits trace, parent and clock from the calling context's
        current span; with none open — or `root=True`, which also opens
        a new trace — it stands alone on `clock` (default SYSTEM)."""
        if not self.enabled:
            return NOP_SPAN
        if root:
            return Span(
                self, next(_ids), 0, subsystem, name, clock or SYSTEM, attrs, True
            )
        if ctx is None:
            ctx = _current.get()
            if ctx is None:
                return Span(self, 0, 0, subsystem, name, clock or SYSTEM, attrs)
        return Span(
            self, ctx.trace_id, ctx.span_id, subsystem, name, clock or ctx.clock, attrs
        )

    def emit(
        self,
        subsystem: str,
        name: str,
        *,
        duration_s: float = 0.0,
        clock: Clock | None = None,
        **attrs,
    ) -> None:
        """Point-in-time event (attach attempt, breaker trip): a span of
        the given duration ending now, a child of the calling context's
        current span where one is open."""
        if not self.enabled:
            return
        now = (clock or SYSTEM).monotonic()
        cur = _current.get()
        trace_id, parent_id = (cur.trace_id, cur.span_id) if cur is not None else (0, 0)
        self._append(
            trace_id, subsystem, name, now - duration_s, duration_s, attrs or None,
            0, parent_id,
        )

    # -- introspection ---------------------------------------------------

    def __len__(self) -> int:
        return len(self._ring)

    @property
    def dropped(self) -> int:
        return self.recorded - len(self._ring)

    def dump(
        self, n: int | None = None, *, subsystem: str | None = None,
        trace_id: int | None = None,
    ) -> list[dict]:
        """Last `n` spans (oldest first) as JSON-ready dicts, optionally
        filtered by subsystem or trace id. `thread` is the recording
        thread's name where it is still alive, else its id (an id the
        system has handed to a later thread reads that thread's name)."""
        spans = list(self._ring)
        names = {t.ident: t.name for t in threading.enumerate()}
        out = []
        for tid, sub, name, start, dur, attrs, sid, pid, thread, cpu, proc in spans:
            if subsystem is not None and sub != subsystem:
                continue
            if trace_id is not None and tid != trace_id:
                continue
            d = {
                "trace_id": tid,
                "span_id": sid,
                "parent_id": pid,
                "subsystem": sub,
                "name": name,
                "start_s": round(start, 6),
                "duration_ms": round(dur * 1e3, 4),
                "thread": names.get(thread, thread),
            }
            if cpu is not None:
                d["cpu_ms"] = round(cpu * 1e3, 4)
            if proc is not None:
                d["proc_cpu_ms"] = round(proc * 1e3, 4)
            if attrs:
                d["attrs"] = attrs
            out.append(d)
        if n is not None:
            out = out[-n:]
        return out

    def stats(self) -> dict:
        return {
            "enabled": self.enabled,
            "ring_size": self.ring_size,
            "spans": len(self._ring),
            "recorded": self.recorded,
            "dropped": self.dropped,
            "auto_dumps": list(self.dumps),
        }

    def auto_dump(self, reason: str) -> str | None:
        """Dump the ring because something went wrong (loop wedge, hub
        timeout, breaker trip). Returns the file path when `out_dir` is
        set, else records the event in-memory only. Diagnostics must
        never raise into the caller."""
        if not self.enabled:
            return None
        entry: dict = {"reason": reason, "spans": len(self._ring)}
        path = None
        if self.out_dir:
            try:
                os.makedirs(self.out_dir, exist_ok=True)
                # reasons reach here from operator input too
                # (/debug/flight?dump=<reason>) — keep the filename flat
                safe = re.sub(r"[^A-Za-z0-9._-]+", "_", reason) or "dump"
                path = os.path.join(
                    self.out_dir, f"flight-{safe}-{next(self._dump_seq)}.json"
                )
                with open(path, "w", encoding="utf-8") as f:
                    json.dump({"reason": reason, "spans": self.dump()}, f)
                entry["path"] = path
            except Exception as e:  # noqa: BLE001 — diagnostics must not raise
                logger.warning("flight dump for %r failed: %r", reason, e)
                path = None
        self.dumps.append(entry)
        logger.error(
            "flight recorder dumped (%s): %d spans%s",
            reason,
            len(self._ring),
            f" -> {path}" if path else "",
        )
        return path

    def clear(self) -> None:
        self._ring.clear()
        self.recorded = 0
        self.dumps.clear()


def _env_enabled(default: bool) -> bool:
    v = os.environ.get("TMTPU_TRACE")
    if v is None or v == "":
        return default
    return v.lower() not in ("0", "false", "no")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if not v:
        return default
    try:
        return int(v)
    except ValueError:
        # a malformed diagnostics knob must not kill the process: trace
        # is imported at module level by the whole verify funnel
        logger.warning("ignoring malformed %s=%r (want an int)", name, v)
        return default


#: THE process recorder. Module import reads the env so library users
#: (and tests that set TMTPU_TRACE before import) get the right mode
#: without any node wiring.
RECORDER = FlightRecorder(
    enabled=_env_enabled(True),
    ring_size=_env_int("TMTPU_TRACE_RING", DEFAULT_RING),
    out_dir=os.environ.get("TMTPU_TRACE_DIR", ""),
)


#: set once the first Node applied its `[trace]` section — the recorder
#: is process-wide, so a later node's (possibly default) config must not
#: silently clobber the first one's dump_dir/enabled mid-run
_node_configured = False


def configure_once(
    enabled: bool | None = None,
    ring_size: int | None = None,
    out_dir: str | None = None,
) -> bool:
    """Node-boot hook: apply `[trace]` config the FIRST time a node in
    this process starts; later nodes (multi-node tests, harnesses) are
    no-ops. Returns whether this call configured the recorder. Tests
    that need to reconfigure use `configure` / RECORDER directly."""
    global _node_configured
    if _node_configured:
        return False
    _node_configured = True
    configure(enabled=enabled, ring_size=ring_size, out_dir=out_dir)
    return True


def configure(
    enabled: bool | None = None,
    ring_size: int | None = None,
    out_dir: str | None = None,
) -> FlightRecorder:
    """Apply `[trace]` config to the process recorder. Env wins over
    explicit values (the same contract as the TMTPU_VERIFYHUB_* knobs):
    an operator exporting TMTPU_TRACE=0 silences every in-process node
    regardless of TOML."""
    if enabled is not None:
        RECORDER.enabled = _env_enabled(enabled)
    if ring_size is not None:
        size = _env_int("TMTPU_TRACE_RING", ring_size)
        if size != RECORDER.ring_size:
            RECORDER.ring_size = max(1, size)
            RECORDER._ring = deque(RECORDER._ring, maxlen=RECORDER.ring_size)
    if out_dir is not None:
        RECORDER.out_dir = os.environ.get("TMTPU_TRACE_DIR", "") or out_dir
    return RECORDER


# -- module-level conveniences (the names call sites import) ---------------


def is_enabled() -> bool:
    return RECORDER.enabled


def start(clock: Clock | None = None) -> TraceCtx | None:
    return RECORDER.start(clock)


def record(ctx, subsystem, name, start_s, end_s, **attrs) -> None:
    RECORDER.record(ctx, subsystem, name, start_s, end_s, **attrs)


def finish(ctx, subsystem, name, **attrs) -> None:
    RECORDER.finish(ctx, subsystem, name, **attrs)


def span(subsystem, name, *, ctx=None, clock=None, root=False, **attrs):
    return RECORDER.span(subsystem, name, ctx=ctx, clock=clock, root=root, **attrs)


def emit(subsystem, name, *, duration_s=0.0, clock=None, **attrs) -> None:
    RECORDER.emit(subsystem, name, duration_s=duration_s, clock=clock, **attrs)


def auto_dump(reason: str) -> str | None:
    return RECORDER.auto_dump(reason)
