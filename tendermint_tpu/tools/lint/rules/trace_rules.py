"""Span-discipline analyzer: the flight recorder must stay honest.

Two invariants guard the tracing layer (libs/trace.py):

  * **Context-manager spans only.** `trace.span(...)` returns a Span
    whose duration is recorded on `__exit__`. A span held in a variable
    (or a bare call whose result is dropped) without a `with` is never
    closed — it silently under-reports and leaks the object. The
    explicit-boundary APIs (`record`, `emit`, `finish`) are exempt:
    they are closed by construction.

  * **No wall clock in trace code.** Spans live in the injectable
    Clock's monotonic duration domain. `time.time()` / `datetime.now()`
    inside the trace/telemetry layer would stamp nondeterministic wall
    time into dumps compared across same-seed chaos runs, and a future
    refactor could leak it into seeded paths. (`time.monotonic` is the
    duration domain and stays legal — `libs/clock.Clock.monotonic` is
    built on it.)

  * **No row per item on the verify funnel** (`span-per-item`). In
    `crypto/` and `types/validation.py` the loops run per signature —
    thousands a block-sync range. A `trace.span`/`record`/`emit` inside
    a `for`/`while` there is one ring row (and one profiler annotation)
    per iteration: the span goes AROUND the loop, with `n=` on it. A
    loop that is bounded by something else (chunks, dispatches, the
    traces a batch joined) says so in a pragma.
"""

from __future__ import annotations

import ast
from typing import Iterable

from ..framework import FileContext, Finding, Rule


class SpanDiscipline(Rule):
    id = "span-discipline"
    doc = (
        "trace spans must be opened via `with trace.span(...)` (never "
        "held/dropped), and trace/telemetry code must not read the wall "
        "clock (time.time/datetime.now)"
    )
    scope = None  # span-usage half scans everywhere trace is used
    profiles = ("node", "tests")

    #: files that ARE the tracing/observability layer: the
    #: no-wall-clock half applies (watchdog.py is allowlisted — wedge
    #: reports deliberately carry operator-facing wall timestamps)
    WALL_CLOCK_SCOPE = (
        "tendermint_tpu/libs/trace.py",
        "tendermint_tpu/libs/watchdog.py",
        "tendermint_tpu/crypto/backend_telemetry.py",
        "scripts/tracectl.py",
    )

    WALL_CALLS = {
        "time.time",
        "time.time_ns",
        "time.strftime",
        "time.localtime",
        "time.gmtime",
        "datetime.now",
        "datetime.utcnow",
        "datetime.datetime.now",
        "datetime.datetime.utcnow",
        "date.today",
    }

    #: span-opening call names (resolved through the import table):
    #: module-level helper and recorder/module attribute forms
    SPAN_OPENERS = ("trace.span", "tendermint_tpu.libs.trace.span")

    def _is_span_call(self, ctx: FileContext, node: ast.Call) -> bool:
        name = ctx.resolve_call(node)
        if name is None:
            return False
        if name in self.SPAN_OPENERS or name.endswith(".trace.span"):
            return True
        # RECORDER.span(...) / recorder.span(...): attribute call whose
        # receiver is a recorder-ish name — matched conservatively so
        # unrelated `.span()` methods elsewhere don't trip the rule
        if isinstance(node.func, ast.Attribute) and node.func.attr == "span":
            recv = node.func.value
            if isinstance(recv, ast.Name) and recv.id.lower().endswith("recorder"):
                return True
            resolved = ctx.resolve_call(node)
            if resolved and resolved.startswith(("trace.", "RECORDER.")):
                return True
        return False

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        in_trace_layer = ctx.rel == "tendermint_tpu/libs/trace.py"
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            name = ctx.resolve_call(node)
            if (
                ctx.rel in self.WALL_CLOCK_SCOPE
                and name in self.WALL_CALLS
            ):
                yield ctx.finding(
                    self.id,
                    node,
                    f"wall-clock read `{name}()` in the tracing layer: spans "
                    "live in the injectable Clock's monotonic duration domain "
                    "(libs/clock.Clock.monotonic) so dumps stay comparable "
                    "across same-seed chaos runs",
                )
                continue
            if in_trace_layer or not self._is_span_call(ctx, node):
                continue
            parent = ctx.parents.get(node)
            # legal: the call is (one of) the context expression(s) of a
            # `with`/`async with` item
            if isinstance(parent, ast.withitem):
                continue
            yield ctx.finding(
                self.id,
                node,
                "span opened outside a `with` block: the Span only records "
                "on __exit__, so holding or dropping it silently loses the "
                "measurement — use `with trace.span(...) as sp:` (or the "
                "closed-by-construction record()/emit() APIs)",
            )


class SpanPerItem(Rule):
    id = "span-per-item"
    doc = (
        "no trace.span/record/emit inside a loop in crypto/ or "
        "types/validation.py: per-signature work is one span around the "
        "loop with n= on it"
    )
    scope = ("tendermint_tpu/crypto/", "tendermint_tpu/types/validation.py")

    RECORDING = ("span", "record", "emit", "finish")
    LOOPS = (
        ast.For, ast.AsyncFor, ast.While,
        ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp,
    )

    def _is_recording_call(self, ctx: FileContext, node: ast.Call) -> bool:
        name = ctx.resolve_call(node)
        if name is None:
            return False
        head, _, attr = name.rpartition(".")
        return attr in self.RECORDING and (
            head == "trace" or head.endswith(".trace") or head.endswith("RECORDER")
        )

    def check(self, ctx: FileContext) -> Iterable[Finding]:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not self._is_recording_call(ctx, node):
                continue
            cur = ctx.parents.get(node)
            while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                if isinstance(cur, self.LOOPS):
                    yield ctx.finding(
                        self.id,
                        node,
                        "flight-recorder row inside a loop on the verify "
                        "funnel: loops here run per signature, and a row "
                        "each floods the ring (thousands a range) — put ONE "
                        "span around the loop with n= on it, or say what "
                        "bounds this loop in a pragma",
                    )
                    break
                cur = ctx.parents.get(cur)


RULES = (SpanDiscipline(), SpanPerItem())
