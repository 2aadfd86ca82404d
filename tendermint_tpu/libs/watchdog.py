"""Event-loop liveness watchdog — the asyncio analog of the reference's
deadlock-detecting mutexes (internal/libs/sync/deadlock.go:1-6, which
swap in go-deadlock when build-tagged).

Go detects a mutex held too long; the equivalent failure mode in a
single-threaded asyncio node is the LOOP wedging: a coroutine doing
blocking I/O / CPU inline, or a genuine deadlock between tasks awaiting
each other. Either way the symptom is identical — the loop stops
scheduling — and the diagnosis needs the same artifact Go prints: where
everything is stuck.

LoopWatchdog runs a daemon THREAD (it must live off the loop to observe
the loop being stuck) that schedules a trivial heartbeat callback via
`call_soon_threadsafe` and waits. If the heartbeat doesn't run within
`threshold_s`, it writes every thread's Python stack and every asyncio
task's stack to `<dir>/wedged-<ts>.txt` and logs loudly. One report per
wedge (re-armed once the loop breathes again) — a wedged loop that
recovers produces exactly one bundle, not a spray. A wedge also dumps
the flight recorder (`libs/trace.auto_dump`): the spans leading up to
the stall are the other half of the diagnosis.

BackendInitWatchdog is the other watchdog here: accelerator backend
init (the first jax.devices() on a locally attached chip) normally
answers in seconds, but it runs inside a node that must keep verifying
meanwhile, and a chip another process holds makes it block or fail. The
watchdog bounds it — short attempts plus a cheap periodic probe of
earlier (still running) attempts — and records every attempt into
`crypto/backend_telemetry`, so attach behavior is visible in /metrics
and trace dumps.
"""

from __future__ import annotations

import io
import logging
import os
import threading
import time
import traceback

logger = logging.getLogger("libs.watchdog")


class LoopWatchdog:
    """Watches one asyncio loop from a side thread.

    start() must be called from the loop's thread (it captures the
    running loop); stop() from anywhere."""

    def __init__(
        self,
        out_dir: str,
        *,
        threshold_s: float = 5.0,
        interval_s: float = 2.0,
    ):
        self.out_dir = out_dir
        self.threshold_s = threshold_s
        self.interval_s = interval_s
        self._loop = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._beat = threading.Event()
        self.reports: list[str] = []  # paths of wedge reports written

    def start(self) -> None:
        import asyncio

        self._loop = asyncio.get_running_loop()
        self._thread = threading.Thread(
            target=self._run, name="loop-watchdog", daemon=True
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        # wake a thread parked in _beat.wait() immediately — without this,
        # stop() called FROM the loop thread would deadlock against its
        # own queued heartbeat for up to threshold_s
        self._beat.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    # -- internals -------------------------------------------------------

    def _run(self) -> None:
        wedged = False
        while not self._stop.is_set():
            self._beat.clear()
            try:
                self._loop.call_soon_threadsafe(self._beat.set)
            except RuntimeError:
                return  # loop closed
            responded = self._beat.wait(self.threshold_s)
            if self._stop.is_set():
                return
            if not responded and not wedged:
                wedged = True
                self._report()
                try:
                    from . import trace

                    trace.auto_dump("loop-wedged")
                except Exception as e:  # noqa: BLE001 — diagnostics only
                    logger.debug("flight dump on wedge failed: %r", e)
            elif responded:
                wedged = False
            self._stop.wait(self.interval_s)

    def _report(self) -> None:
        buf = io.StringIO()
        buf.write(
            f"=== event loop unresponsive for >{self.threshold_s}s "
            f"at {time.strftime('%Y-%m-%dT%H:%M:%S')} ===\n\n"
        )
        frames = {t.ident: t.name for t in threading.enumerate()}
        import sys

        for ident, frame in sys._current_frames().items():
            buf.write(f"--- thread {frames.get(ident, ident)} ---\n")
            buf.write("".join(traceback.format_stack(frame)))
            buf.write("\n")
        # task stacks: enumerable from outside the loop thread —
        # all_tasks(loop) only reads the weak set
        try:
            import asyncio

            for task in asyncio.all_tasks(self._loop):
                state = (
                    "cancelled"
                    if task.cancelled()
                    else "done" if task.done() else "pending"
                )
                buf.write(f"--- task {task.get_name()} ({state}) ---\n")
                stack = task.get_stack()
                for f in stack:
                    buf.write("".join(traceback.format_stack(f)[-1:]))
            buf.write("\n")
        except Exception as e:  # noqa: BLE001 — diagnostics must not raise
            buf.write(f"(task enumeration failed: {e!r})\n")
        os.makedirs(self.out_dir, exist_ok=True)
        path = os.path.join(self.out_dir, f"wedged-{int(time.time()*1000)}.txt")
        with open(path, "w") as f:
            f.write(buf.getvalue())
        self.reports.append(path)
        logger.error(
            "event loop wedged >%ss; stacks dumped to %s", self.threshold_s, path
        )


class BackendInitWatchdog:
    """Bounded-retry, watchdogged backend init.

    `run(fn)` executes `fn` on a daemon thread with a per-attempt
    timeout. A hung attempt is NOT a verdict: Python cannot kill the
    thread (jax backend init holds a global lock), so the thread keeps
    running and every later poll cheaply re-checks whether it finished
    late — a device that comes up at t=70 s is adopted by the attempt
    that timed out at t=60 s, instead of being thrown away. Each
    attempt (latency, outcome, error) is recorded into
    `crypto/backend_telemetry` (-> /metrics + flight-recorder spans)
    and kept in `self.log` for callers that serialize the story.
    `crypto/batch._probe_tpu` runs the node-side attach behind this.
    """

    def __init__(
        self,
        *,
        attempts: int = 3,
        timeout_s: float = 60.0,
        backoff_s: float = 5.0,
        poll_s: float = 1.0,
        name: str = "backend-init",
    ):
        self.attempts = max(1, attempts)
        self.timeout_s = timeout_s
        self.backoff_s = backoff_s
        self.poll_s = max(0.05, poll_s)
        self.name = name
        #: structured per-attempt records: {latency_s, outcome, error?}
        self.log: list[dict] = []

    def _spawn(self, fn) -> dict:
        slot: dict = {"t0": time.monotonic()}

        def runner():
            try:
                slot["result"] = fn()
            except Exception as e:  # noqa: BLE001 — reported per attempt
                slot["error"] = e
            slot["elapsed"] = time.monotonic() - slot["t0"]

        t = threading.Thread(target=runner, name=self.name, daemon=True)
        slot["thread"] = t
        t.start()
        return slot

    @staticmethod
    def _settled(slot: dict) -> bool:
        return "result" in slot or "error" in slot

    def run(self, fn):
        """Returns `fn()`'s result when truthy, or None when every
        bounded attempt raised, returned falsy, or hung (the caller
        picks its fallback). Never raises."""
        from ..crypto import backend_telemetry as bt

        outstanding: list[dict] = []
        for i in range(self.attempts):
            slot = self._spawn(fn)
            outstanding.append(slot)
            deadline = time.monotonic() + self.timeout_s
            while time.monotonic() < deadline:
                # cheap probe: any attempt (this one OR an earlier hung
                # one that finished late) settling ends the wait
                for s in outstanding:
                    if self._settled(s):
                        break
                else:
                    slot["thread"].join(self.poll_s)
                    continue
                break
            settled = next((s for s in outstanding if s.get("result")), None)
            if settled is not None:
                latency = settled.get("elapsed", time.monotonic() - settled["t0"])
                self.log.append({"latency_s": round(latency, 3), "outcome": "ok"})
                bt.record_attach_attempt(latency, True)
                return settled["result"]
            # a clean falsy return ("no backend here") is a FAILED
            # attempt, not a success: telemetry must not count it as an
            # attach, and the bounded retries still apply — a device can
            # answer "not yet" before it answers "ready"
            unavailable = next((s for s in outstanding if "result" in s), None)
            failed = next((s for s in outstanding if "error" in s), None)
            if unavailable is not None:
                outstanding.remove(unavailable)
                latency = unavailable.get(
                    "elapsed", time.monotonic() - unavailable["t0"]
                )
                self.log.append(
                    {"latency_s": round(latency, 3), "outcome": "unavailable"}
                )
                bt.record_attach_attempt(latency, False, error="unavailable")
                logger.warning(
                    "%s attempt %d/%d: backend unavailable after %.1fs",
                    self.name, i + 1, self.attempts, latency,
                )
            elif failed is not None:
                outstanding.remove(failed)
                latency = failed.get("elapsed", time.monotonic() - failed["t0"])
                err = repr(failed["error"])
                self.log.append(
                    {"latency_s": round(latency, 3), "outcome": "error", "error": err}
                )
                bt.record_attach_attempt(latency, False, error=err)
                logger.warning(
                    "%s attempt %d/%d failed after %.1fs: %s",
                    self.name, i + 1, self.attempts, latency, err,
                )
            else:
                latency = time.monotonic() - slot["t0"]
                self.log.append(
                    {"latency_s": round(latency, 3), "outcome": "hung"}
                )
                bt.record_attach_attempt(latency, False, error="hung")
                logger.warning(
                    "%s attempt %d/%d hung past %.0fs (thread left running; "
                    "later attempts keep probing it)",
                    self.name, i + 1, self.attempts, self.timeout_s,
                )
            if i < self.attempts - 1 and self.backoff_s:
                time.sleep(self.backoff_s * (i + 1))
        return None
