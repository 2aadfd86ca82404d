"""VerifyHub — node-wide micro-batching signature-verification scheduler.

Every subsystem that needs a signature checked (live-consensus vote
intake, proposal verification, the evidence pool, the light client, the
verify_commit* funnel) submits ``(pubkey, sign_bytes, sig)`` to the hub
and awaits a per-item verdict. The hub coalesces concurrent requests
into hardware-sized batches — the shared-verification-engine shape the
committee-consensus (arXiv:2302.00418) and FPGA-ECDSA (arXiv:2112.02229)
measurements point at — and hands each dispatch, whatever its key types,
to ONE `crypto.batch.AdaptiveBatchVerifier`, so the TPU circuit breaker,
CPU re-verify fallback and measured routing cutoff all apply unchanged.
The hub does not partition by scheme itself: the verifier does (Edwards
rows in one MSM dispatch, BLS on the pairing path, rows of a key type
with no batch kernel — secp256k1 — on its host lane, pool tasks started
before the device partitions and joined after them, each partition
counted under its own route), exactly as for a caller without a hub.

Scheduling model (one dispatcher thread + one device-runner thread):

  * requests land in one of two FIFO lanes — ``live`` (consensus votes
    and proposals on the hot path) and ``backfill`` (block-sync /
    state-sync / light-client catch-up traffic). Each dispatch packs
    the live lane FIRST and only then fills the remaining batch
    capacity from backfill, so a node replaying history can saturate
    the device without ever starving the vote it needs to commit the
    next block. Identical in-flight triples COALESCE onto one entry
    (gossip hands every vote to a node several times — the duplicate
    attaches its future to the pending verify instead of re-entering
    the queue); a live submission coalescing onto a queued backfill
    entry PROMOTES it into the live lane (a queued backfill group goes
    over whole);
  * a bounded LRU of already-verified ``(key_type, pubkey, sha256(msg),
    sig)`` verdicts answers repeats without any dispatch at all;
  * dispatch fires when a device-sized batch fills, when the adaptive
    micro-batch window expires, or immediately for *urgent* requests
    (the sync facade — a caller blocking the event loop must not pay a
    coalescing tax it can never recoup). `max_batch` and `window_ms`
    govern these LONE requests: how many of them make a dispatch, how
    long one lingers for company;
  * a GROUP (`verify_many`: every signature of a commit, a block-sync
    range's thousands) is ONE unit from submit to settle: one
    acquisition of the lock, one pass over its rows for the verdict LRU
    and for coalescing (both still per row), one queue entry holding
    its cold rows, one wake-up of the dispatcher, one thing to wait on.
    It is flushed at once and never split: a dispatch takes the lone
    requests first (live lane, then backfill, up to `max_batch`), then
    the first queued group WHOLE whatever its size, and further groups
    only while the dispatch stays within `max_batch` rows. A dispatch
    of more rows than that goes to the device at the verifier's chunk
    shape (its 8,192-row program — the one every start warms last —
    which also cuts a larger group into chunks) and not at the ladder
    rung of its row count: a range is as many blocks as were
    downloaded, and a shape a size would be a compile a size in the
    middle of a catch-up;
  * the window ADAPTS to measured occupancy: an EWMA of signatures per
    dispatch shrinks the window toward zero under light load and
    stretches it back to the configured ceiling as concurrency appears;
  * dispatch is double-buffered: at most two batches are in flight
    (one executing, one queued at the runner — more adds queueing, not
    overlap). The dispatcher waits for a free slot BEFORE packing and
    packs at the last possible moment, so an urgent/live arrival during
    a full double buffer still makes the very next dispatch instead of
    sitting behind a pre-packed backfill batch.

The hub is process-wide (like the TPU backend it feeds): `acquire_hub` /
`release_hub` refcount node lifecycles, and in-process multi-node tests
deliberately share one hub so cross-node duplicate votes dedup too.
When no hub is running every helper falls back to direct host
verification — unit tests and library users pay nothing.

Mesh awareness: `max_batch` is a PER-CHIP target. Each dispatch
iteration reads the active device-mesh size (crypto/batch
`mesh_parallelism`, fed by the per-device breakers in
crypto/tpu/mesh.py) and scales both the pack capacity and the adaptive
window's ramp by it — an 8-chip mesh fills 8-chip-sized micro-batches,
and a breaker-degraded mesh shrinks them the same iteration. Sharded
dispatches stamp per-device shard occupancy onto their hub.dispatch
spans (scripts/tracectl.py --per-device).

Remote route (crypto/verifyd.py): when ``TMTPU_VERIFYD_SOCK`` /
``[verify_hub] verifyd_sock`` points at a verifyd sidecar's Unix socket,
`_verify_batch` ships its packed cold batches to the daemon instead of
dispatching locally — the adaptive window, verdict cache, coalescing and
lanes all stay client-side, so the socket carries only what the local
cache could not answer, and the daemon re-batches across every client
process on the host (one warm device mesh, N node processes). Any
remote failure degrades to the local path below through a circuit
breaker, exactly like the TPU→CPU degrade.

Env knobs (override per-node config): TMTPU_VERIFYHUB_DISABLE=1,
TMTPU_VERIFYHUB_BATCH, TMTPU_VERIFYHUB_WINDOW_MS, TMTPU_VERIFYHUB_CACHE,
TMTPU_MESH_SCALE=0 (pin single-chip batch sizing), TMTPU_VERIFYD_SOCK
(remote sidecar route).
"""

from __future__ import annotations

import asyncio
import logging
import os
import threading
import time
from collections import Counter, OrderedDict, deque
from concurrent.futures import Future, ThreadPoolExecutor

from ..libs import trace
from ..libs.metrics import Histogram
from . import PubKey
from .batch import AdaptiveBatchVerifier, rows_by_lane
from .hashes import sha256

logger = logging.getLogger("crypto.verify_hub")

#: scheduler lanes: live consensus is packed ahead of catch-up backfill
#: in every micro-batch (see module docstring)
LANE_LIVE = "live"
LANE_BACKFILL = "backfill"
LANES = (LANE_LIVE, LANE_BACKFILL)

#: queue-latency buckets (seconds) — sub-millisecond resolution, because
#: the whole point of the micro-batch window is single-digit-ms latency
LATENCY_BUCKETS = (
    0.0005, 0.001, 0.002, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 1.0,
)


class _Pending:
    """One unique (pubkey, msg, sig) triple awaiting a verdict. Duplicate
    submissions while it is queued/in flight append their futures here
    (and their trace contexts — a coalesced gossip duplicate's trace
    still gets the dispatch's hub.queue/hub.execute spans)."""

    __slots__ = (
        "key", "pub_key", "msg", "sig", "futures", "enqueued_at", "lane", "traces",
        "tenants",
    )

    def __init__(
        self, key, pub_key, msg, sig, fut, now, lane, trace_ctx=None, tenant=None
    ):
        self.key = key
        self.pub_key = pub_key
        self.msg = msg
        self.sig = sig
        # whoever waits for this verdict: a Future (a lone request) or a
        # (_Group, row index) pair (a group's row that coalesced onto it)
        self.futures: list = [fut]
        self.enqueued_at = now
        self.lane = lane
        # (ctx, joined_at): a coalesced duplicate's queue wait starts
        # when IT joined, not when the first submitter enqueued — else
        # its queue span would begin before its own trace did
        self.traces: list | None = [(trace_ctx, now)] if trace_ctx is not None else None
        # multi-tenant tag (the verifyd daemon stamps each client's
        # connection id): a dispatch whose batch carries >1 distinct
        # tenant is a cross-client pack — the sidecar's amortization
        # win, counted instead of assumed
        self.tenants: set | None = {tenant} if tenant is not None else None

    def add_tenant(self, tenant) -> None:
        if tenant is None:
            return
        if self.tenants is None:
            self.tenants = {tenant}
        else:
            self.tenants.add(tenant)

    def add_trace(self, trace_ctx) -> None:
        if trace_ctx is None:
            return
        entry = (trace_ctx, time.monotonic())
        if self.traces is None:
            self.traces = [entry]
        else:
            self.traces.append(entry)


class _Row:
    """One cold row of a group: what `_verify_batch` reads of a
    `_Pending` (key, triple, lane) and the row's place in the caller's
    list. Its lane is its group's: a promoted group takes its rows along."""

    __slots__ = ("key", "pub_key", "msg", "sig", "group", "index")

    def __init__(self, key, pub_key, msg, sig, group, index):
        self.key = key
        self.pub_key = pub_key
        self.msg = msg
        self.sig = sig
        self.group = group
        self.index = index

    @property
    def lane(self) -> str:
        return self.group.lane


class _Group:
    """One `verify_many` call: the queue entry that holds its cold rows
    and the one thing its caller waits on. `results` is the caller's
    list, filled in place — cache hits at submit, the rest as their
    dispatches (its own, or the one a coalesced row rides) settle;
    `remaining` counts the rows still out and is only touched under the
    hub's lock."""

    __slots__ = (
        "results", "remaining", "rows", "lane", "enqueued_at", "traces", "joiners",
        "queued", "error", "_done",
    )

    def __init__(self, n: int, lane: str, now: float):
        self.results: list = [None] * n
        self.remaining = 0
        self.rows: list[_Row] = []
        self.lane = lane
        self.enqueued_at = now
        # [ctx, joined_at, rows]: the caller's trace with the group's own
        # rows, and one entry a row another trace's group coalesced here
        self.traces: list = []
        # row -> whoever else waits for it (as `_Pending.futures`)
        self.joiners: dict | None = None
        self.queued = False
        self.error: Exception | None = None
        self._done = threading.Event()

    def join(self, row: _Row, waiter) -> None:
        if self.joiners is None:
            self.joiners = {}
        self.joiners.setdefault(row, []).append(waiter)

    def wake(self) -> None:
        self._done.set()

    def fail(self, e: Exception) -> None:
        if self.error is None:
            self.error = e
        self._done.set()

    def wait(self, timeout: float | None) -> list[bool]:
        if not self._done.wait(timeout):
            raise TimeoutError(f"{self.remaining} of {len(self.results)} verdicts outstanding")
        if self.error is not None:
            raise self.error
        return self.results


def _cache_key(pub_key: PubKey, msg: bytes, sig: bytes) -> tuple:
    # hash the message so cache entries stay O(1)-sized regardless of
    # sign-bytes length; the pubkey+sig stay verbatim (fixed width)
    return (pub_key.TYPE, pub_key.bytes(), sha256(msg), sig)


class VerifyHub:
    """Per-process async verification service (see module docstring)."""

    #: in-flight dispatch depth: one batch on the device, one packed and
    #: waiting — the double buffer. More adds queueing, not overlap.
    MAX_INFLIGHT_BATCHES = 2

    def __init__(
        self,
        *,
        max_batch: int | None = None,
        window_ms: float | None = None,
        cache_size: int | None = None,
        adaptive: bool = True,
        mesh_scale: bool | None = None,
        verifyd_sock: str | None = None,
        allow_remote: bool = True,
        name: str = "verify-hub",
    ):
        # env wins over explicit kwargs (the node always passes its
        # config values, and the documented contract is that the env
        # knobs override per-node config for ops/testing); fallback
        # defaults come from VerifyHubConfig — one source of truth
        from ..config import VerifyHubConfig

        defaults = VerifyHubConfig()

        def _knob(env_name, explicit, default, cast):
            v = os.environ.get(env_name)
            if v:
                return cast(v)
            return default if explicit is None else explicit

        max_batch = _knob("TMTPU_VERIFYHUB_BATCH", max_batch, defaults.max_batch, int)
        window_ms = _knob(
            "TMTPU_VERIFYHUB_WINDOW_MS", window_ms, defaults.window_ms, float
        )
        cache_size = _knob(
            "TMTPU_VERIFYHUB_CACHE", cache_size, defaults.cache_size, int
        )
        mesh_scale = _knob(
            "TMTPU_MESH_SCALE",
            mesh_scale,
            defaults.mesh_scale,
            lambda v: v.lower() not in ("0", "false", "no"),
        )
        # remote verification sidecar (crypto/verifyd.py): when a socket
        # path is configured, _verify_batch ships packed cold batches to
        # the verifyd daemon instead of dispatching locally — the cache,
        # window, coalescing and lanes above all stay client-side.
        # allow_remote=False is the daemon's own hub (it must never
        # route back into itself); not env-overridable by design.
        if allow_remote:
            verifyd_sock = _knob(
                "TMTPU_VERIFYD_SOCK", verifyd_sock, defaults.verifyd_sock, str
            )
        else:
            verifyd_sock = ""
        self.verifyd_sock = verifyd_sock or ""
        self.name = name
        self.max_batch = max(1, max_batch)
        self.window_s = max(0.0, window_ms) / 1e3
        self.cache_size = max(0, cache_size)
        self.adaptive = adaptive
        #: scale batch capacity + window by the active mesh size: 8
        #: chips fed single-chip-sized batches run at 1/8 occupancy
        self.mesh_scale = bool(mesh_scale)
        self._mesh_n = 1  # refreshed once per dispatch iteration

        self._cv = threading.Condition()
        # two FIFO lanes; dispatch packs live first, then backfill
        self._queues: dict[str, OrderedDict[tuple, _Pending]] = {
            lane: OrderedDict() for lane in LANES
        }
        self._inflight: dict[tuple, _Pending] = {}
        # groups (verify_many) queue beside the lone requests of their
        # lane, each ONE entry; every cold row of a queued or in-flight
        # group is findable by its key, for coalescing
        self._groups: dict[str, deque[_Group]] = {lane: deque() for lane in LANES}
        self._group_rows: dict[tuple, _Row] = {}
        self._cache: OrderedDict[tuple, bool] = OrderedDict()
        self._urgent = False
        self._running = False
        self._thread: threading.Thread | None = None
        self._runner: ThreadPoolExecutor | None = None
        self._slots = threading.BoundedSemaphore(self.MAX_INFLIGHT_BATCHES)
        self._worker_ids: set[int] = set()
        # per-worker-thread route of the batch just verified (trace attrs)
        self._route_local = threading.local()
        # occupancy EWMA seeds at max_batch: start optimistic (full
        # window) and adapt DOWN — the first dispatches under light load
        # pay at most one window, never a stuck-small window under load
        self._ewma_occupancy = float(self.max_batch)
        self._started_at = time.monotonic()

        self.latency_hist = Histogram(
            "verifyhub_queue_latency_seconds",
            "submit-to-dispatch wait per request",
            buckets=LATENCY_BUCKETS,
        )
        self._stats = {
            "submitted": 0.0,      # unique triples enqueued
            "dispatches": 0.0,     # batches sent to a verifier
            "dispatched_sigs": 0.0,
            "cache_hits": 0.0,     # answered from the verdict LRU
            "coalesced": 0.0,      # joined an identical in-flight request
            "verify_errors": 0.0,  # batches whose verifier raised
            # per-lane accounting (live packed ahead of backfill)
            "lane_live_submitted": 0.0,
            "lane_backfill_submitted": 0.0,
            "lane_live_dispatched": 0.0,
            "lane_backfill_dispatched": 0.0,
            "lane_promotions": 0.0,  # backfill entries pulled into live
            # dispatched rows by the lane the verifier gives them (it
            # partitions by scheme: ed25519/sr25519 share the Edwards
            # kernel, BLS runs the pairing path, a key type with no batch
            # kernel — secp256k1 — its host lane; rendered as
            # verifyhub_scheme_sigs{scheme=})
            "scheme_edwards_sigs": 0.0,
            "scheme_bls_sigs": 0.0,
            "scheme_host_sigs": 0.0,
            # multi-tenant packing (the verifyd daemon's hub): dispatches
            # whose batch mixed signatures from >1 client connection
            "cross_tenant_dispatches": 0.0,
            # how often the group path engages: groups that entered the
            # queue as one unit, and the cold rows they held
            "bulk_groups": 0.0,
            "bulk_group_sigs": 0.0,
            # where a request's latency goes before its batch runs:
            # summed submit-to-pack wait of every packed request (the
            # queue-latency histogram's sum, as a counter a reader can
            # take deltas of), and the dispatcher's wait for a free
            # in-flight slot (both runners busy)
            "queue_wait_s": 0.0,
            "slot_wait_s": 0.0,
        }

    # -- lifecycle -------------------------------------------------------

    @property
    def is_running(self) -> bool:
        return self._running

    def start(self) -> None:
        if self._running:
            raise RuntimeError(f"{self.name} already started")
        self._running = True
        self._started_at = time.monotonic()
        self._runner = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix=f"{self.name}-runner"
        )
        self._thread = threading.Thread(
            target=self._dispatch_loop, name=f"{self.name}-dispatch", daemon=True
        )
        self._thread.start()

    def stop(self, timeout: float = 30.0) -> None:
        """Clean shutdown: everything already submitted is still
        dispatched and every outstanding future resolves before the
        worker threads exit."""
        with self._cv:
            if not self._running:
                return
            self._running = False
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
        if self._runner is not None:
            self._runner.shutdown(wait=True)
            self._runner = None

    # -- submission ------------------------------------------------------

    def submit_nowait(
        self,
        pub_key: PubKey,
        msg: bytes,
        sig: bytes,
        *,
        urgent: bool = False,
        lane: str = LANE_LIVE,
        trace_ctx=None,
        tenant=None,
    ) -> Future:
        """Enqueue one verification; returns a concurrent Future[bool].

        `urgent` skips the micro-batch window (the batch still takes
        every request queued at dispatch time — urgency costs
        coalescing-with-the-future, not coalescing-with-the-present).
        `lane` picks the scheduler lane: live consensus is packed ahead
        of backfill in every dispatch. `trace_ctx` (libs/trace.TraceCtx)
        joins the request to an end-to-end trace: the hub records
        hub.queue and hub.execute spans on it, one per (dispatch, trace)."""
        if lane not in self._queues:
            # a typo'd lane at a new call site must fail loudly — a
            # silent fall-through to "live" would hand bulk catch-up
            # traffic hot-path priority, the exact starvation the lanes
            # exist to prevent
            raise ValueError(f"unknown verify lane {lane!r}; use one of {LANES}")
        key = _cache_key(pub_key, msg, sig)
        fut: Future = Future()
        run_inline = False
        with self._cv:
            verdict = self._cache.get(key)
            if verdict is not None:
                self._cache.move_to_end(key)
                self._stats["cache_hits"] += 1
                if trace_ctx is not None:
                    # zero-width marker anchored on the TRACE clock: the
                    # trace may time on an injected chaos clock, and a
                    # SYSTEM timestamp would land at a wrong offset in
                    # the per-trace view when rates diverge
                    now = trace_ctx.clock.monotonic()
                    trace.record(trace_ctx, "hub", "cache_hit", now, now, lane=lane)
                fut.set_result(verdict)
                return fut
            pending = self._lone(key)
            if pending is not None:
                pending.futures.append(fut)
                pending.add_trace(trace_ctx)
                pending.add_tenant(tenant)
                self._stats["coalesced"] += 1
                if lane == LANE_LIVE:
                    self._promote(pending)
                if urgent:
                    self._urgent = True
                    self._cv.notify_all()
                return fut
            row = self._group_rows.get(key)
            if row is not None:
                # the triple is a row of a queued or in-flight group
                row.group.join(row, fut)
                if trace_ctx is not None:
                    row.group.traces.append([trace_ctx, time.monotonic(), 1])
                self._stats["coalesced"] += 1
                if lane == LANE_LIVE:
                    self._promote_group(row.group)
                if urgent:
                    self._urgent = True
                    self._cv.notify_all()
                return fut
            if not self._running or threading.get_ident() in self._worker_ids:
                # hub stopped (or a re-entrant call from a hub worker —
                # never wait on ourselves): verify inline below, outside
                # the lock
                run_inline = True
            else:
                q = self._queues[lane]
                q[key] = _Pending(
                    key, pub_key, msg, sig, fut, time.monotonic(), lane,
                    trace_ctx=trace_ctx, tenant=tenant,
                )
                self._stats["submitted"] += 1
                self._stats[f"lane_{lane}_submitted"] += 1
                if urgent:
                    # head of the queue: a blocked caller (the consensus
                    # event loop) jumps any bulk backlog (block-sync
                    # commit groups) instead of waiting FIFO behind it
                    q.move_to_end(key, last=False)
                    self._urgent = True
                self._cv.notify_all()
        if run_inline:
            try:
                fut.set_result(pub_key.verify_signature(msg, sig))
            except Exception as e:  # noqa: BLE001
                fut.set_exception(e)
        return fut

    def verify_sync(
        self,
        pub_key: PubKey,
        msg: bytes,
        sig: bytes,
        timeout: float | None = 60.0,
        *,
        lane: str = LANE_LIVE,
    ) -> bool:
        """Blocking facade for non-async callers (the consensus SM, the
        evidence pool). Urgent: a blocked caller can't generate more
        load, so waiting out the window would be pure added latency."""
        return self.submit_nowait(pub_key, msg, sig, urgent=True, lane=lane).result(
            timeout
        )

    async def verify(
        self,
        pub_key: PubKey,
        msg: bytes,
        sig: bytes,
        *,
        lane: str = LANE_LIVE,
        trace_ctx=None,
    ) -> bool:
        """Async API: awaits the batched verdict without blocking the
        event loop; concurrent awaiters coalesce into one dispatch."""
        return await asyncio.wrap_future(
            self.submit_nowait(pub_key, msg, sig, lane=lane, trace_ctx=trace_ctx)
        )

    def verify_many(
        self,
        items: list[tuple[PubKey, bytes, bytes]],
        timeout: float | None = 300.0,
        *,
        lane: str = LANE_LIVE,
    ) -> list[bool]:
        """Submit a group (every signature of a commit, of a block-sync
        range) and wait for all verdicts, in the caller's order. The
        group is ONE unit (module docstring): its cold rows are one queue
        entry, flushed at once and dispatched whole."""
        # the caller's open span (the commit funnel's validation.verify):
        # the whole group joins its trace ONCE — the hub records queue /
        # execute per (dispatch, trace), never per signature
        ctx = trace.current()
        with trace.span("hub", "submit", n=len(items)) as sp:
            group, answered = self._submit_group(items, lane, ctx)
            sp.set(answered=answered)
        with trace.span("hub", "wait", n=len(items)):
            return group.wait(timeout)

    def _submit_group(self, items, lane: str, trace_ctx) -> tuple[_Group, int]:
        """Enter the hub ONCE for a whole group: (the group, rows already
        answered). Per row what `submit_nowait` does — the verdict LRU,
        then coalescing onto an identical triple queued or in flight, as
        a lone request or as a row of a group (this one's own duplicates
        too) — but under one acquisition of the lock, with no Future and
        no wake-up a row: the cold rows become one queue entry."""
        if lane not in self._queues:
            raise ValueError(f"unknown verify lane {lane!r}; use one of {LANES}")
        # a SHA-256 a row, outside the lock
        keys = [_cache_key(pk, msg, sig) for pk, msg, sig in items]
        group = _Group(len(items), lane, time.monotonic())
        results, rows = group.results, group.rows
        inline: list[int] = []
        with self._cv:
            cache, lone, group_rows = self._cache, self._lone, self._group_rows
            # hub stopped (or a re-entrant call from a hub worker — never
            # wait on ourselves): the cold rows verify inline below
            queueing = self._running and threading.get_ident() not in self._worker_ids
            hits = joined = 0
            for i, key in enumerate(keys):
                verdict = cache.get(key)
                if verdict is not None:
                    cache.move_to_end(key)
                    results[i] = verdict
                    hits += 1
                    continue
                pending = lone(key)
                if pending is not None:
                    pending.futures.append((group, i))
                    pending.add_trace(trace_ctx)
                    if lane == LANE_LIVE:
                        self._promote(pending)
                    joined += 1
                    continue
                row = group_rows.get(key)
                if row is not None:
                    other = row.group
                    other.join(row, (group, i))
                    if other is not group:
                        if trace_ctx is not None:
                            other.traces.append([trace_ctx, group.enqueued_at, 1])
                        if lane == LANE_LIVE:
                            self._promote_group(other)
                    joined += 1
                    continue
                if not queueing:
                    inline.append(i)
                    continue
                pk, msg, sig = items[i]
                row = group_rows[key] = _Row(key, pk, msg, sig, group, i)
                rows.append(row)
            self._stats["cache_hits"] += hits
            self._stats["coalesced"] += joined
            group.remaining = joined + len(rows)
            if rows:
                if trace_ctx is not None:
                    group.traces.append([trace_ctx, group.enqueued_at, len(rows)])
                group.queued = True
                self._groups[lane].append(group)
                self._stats["submitted"] += len(rows)
                self._stats[f"lane_{lane}_submitted"] += len(rows)
                self._stats["bulk_groups"] += 1
                self._stats["bulk_group_sigs"] += len(rows)
            if group.remaining:
                # a blocked caller: no window, whatever it waits for
                self._urgent = True
                self._cv.notify_all()
        for i in inline:
            pk, msg, sig = items[i]
            results[i] = pk.verify_signature(msg, sig)
        if not group.remaining:
            group.wake()
        return group, hits + len(inline)

    def _lone(self, key: tuple) -> _Pending | None:
        """The lone request for `key` that is queued or in flight. Caller
        holds _cv."""
        return (
            self._queues[LANE_LIVE].get(key)
            or self._queues[LANE_BACKFILL].get(key)
            or self._inflight.get(key)
        )

    def _promote(self, pending: _Pending) -> None:
        """A live caller now waits on this triple: pull the still-queued
        backfill entry into the live lane so it stops queueing behind
        bulk catch-up traffic. Caller holds _cv."""
        if pending.lane == LANE_BACKFILL and pending.key in self._queues[LANE_BACKFILL]:
            del self._queues[LANE_BACKFILL][pending.key]
            pending.lane = LANE_LIVE
            self._queues[LANE_LIVE][pending.key] = pending
            self._stats["lane_promotions"] += 1

    def _promote_group(self, group: _Group) -> None:
        """`_promote` for a row of a still-queued backfill group: the
        group is one unit, so it goes over whole. Caller holds _cv."""
        if group.lane == LANE_BACKFILL and group.queued:
            self._groups[LANE_BACKFILL].remove(group)
            group.lane = LANE_LIVE
            self._groups[LANE_LIVE].append(group)
            self._stats["lane_promotions"] += 1

    def flush(self) -> None:
        """Dispatch everything currently queued without waiting out the
        micro-batch window."""
        with self._cv:
            self._urgent = True
            self._cv.notify_all()

    # -- out-of-band verdict cache (aggregate commits) --------------------

    def cached_verdict(self, key: tuple):
        """Consult the verdict LRU for a non-triple key (the aggregate
        commit path: one indivisible pairing-product check has nothing
        to micro-batch, but gossip re-verifications still dedup)."""
        with self._cv:
            v = self._cache.get(key)
            if v is not None:
                self._cache.move_to_end(key)
                self._stats["cache_hits"] += 1
            return v

    def store_verdict(self, key: tuple, ok: bool) -> None:
        with self._cv:
            if self.cache_size:
                self._cache[key] = ok
                self._cache.move_to_end(key)
                while len(self._cache) > self.cache_size:
                    self._cache.popitem(last=False)

    # -- introspection ---------------------------------------------------

    def latency_snapshot(self) -> tuple[list[int], float, int]:
        """Consistent copy of the queue-latency histogram internals
        (observe() runs under the same lock in the dispatcher)."""
        with self._cv:
            h = self.latency_hist
            return list(h._counts), h._sum, h._count

    def stats(self) -> dict:
        with self._cv:
            s = dict(self._stats)
            s["queued"] = float(self._queued())
            for lane in LANES:
                s[f"lane_{lane}_queued"] = float(self._lane_queued(lane))
            s["cache_size"] = float(len(self._cache))
            s["mean_occupancy"] = (
                s["dispatched_sigs"] / s["dispatches"] if s["dispatches"] else 0.0
            )
            s["ewma_occupancy"] = self._ewma_occupancy
            s["mesh_devices"] = float(self._mesh_n)
            s["effective_max_batch"] = float(self._effective_max())
            uptime = max(time.monotonic() - self._started_at, 1e-9)
            s["dispatch_rate"] = s["dispatches"] / uptime
            requests = s["submitted"] + s["cache_hits"] + s["coalesced"]
            s["cache_hit_rate"] = s["cache_hits"] / requests if requests else 0.0
        return s

    # -- scheduling internals --------------------------------------------

    def _refresh_mesh(self) -> int:
        """Active device count, read once per dispatch iteration (the
        mesh registry rate-limits its own recovery probes). Degrades to
        1 on any error — a sick mesh must cost throughput, not dispatch."""
        if self.mesh_scale:
            from .batch import mesh_parallelism

            try:
                self._mesh_n = max(1, mesh_parallelism())
            except Exception:  # noqa: BLE001 — diagnostics only
                self._mesh_n = 1
        else:
            self._mesh_n = 1
        return self._mesh_n

    def _effective_max(self) -> int:
        """Mesh-occupancy-aware batch capacity: one configured max_batch
        PER ACTIVE DEVICE. An 8-chip mesh dispatching single-chip-sized
        batches runs every chip at 1/8 shard occupancy; scaling the
        pack target (and the window ramp below) keeps all chips fed —
        and a per-device breaker degrading the mesh shrinks the target
        the same dispatch loop iteration."""
        return self.max_batch * self._mesh_n

    def _window(self) -> float:
        """Adaptive micro-batch window: scale the configured ceiling by
        recent occupancy, so an idle node's stray vote dispatches
        immediately while a gossip storm fills device-sized batches."""
        if not self.adaptive:
            return self.window_s
        occ = self._ewma_occupancy
        if occ <= 1.0:
            return 0.0
        # linear ramp: full window once recent batches average >= 1/8 of
        # a device batch (past that, latency is already amortized);
        # device batch = per-chip max × active mesh size
        frac = min(1.0, (occ - 1.0) / max(self._effective_max() / 8.0, 1.0))
        return self.window_s * frac

    def _lane_queued(self, lane: str) -> int:
        """Rows queued on a lane: its lone requests and its groups' rows."""
        return len(self._queues[lane]) + sum(len(g.rows) for g in self._groups[lane])

    def _queued(self) -> int:
        return sum(self._lane_queued(lane) for lane in LANES)

    def _dispatch_loop(self) -> None:
        self._worker_ids.add(threading.get_ident())
        while True:
            # refresh the active mesh size OUTSIDE the lock: a degraded
            # device's rate-limited recovery probe is bounded but slow,
            # and submitters must keep filling the lanes meanwhile
            self._refresh_mesh()
            with self._cv:
                while self._running and not self._queued():
                    self._cv.wait(0.2)
                if not self._queued():
                    if not self._running:
                        return
                    continue
                # micro-batch window: linger for more arrivals unless the
                # batch is device-sized (mesh-scaled: one max_batch per
                # active chip), someone is blocked (urgent), or the hub
                # is draining for shutdown
                if self._running:
                    oldest = min(
                        [next(iter(q.values())).enqueued_at for q in self._queues.values() if q]
                        + [g[0].enqueued_at for g in self._groups.values() if g]
                    )
                    deadline = oldest + self._window()
                    while (
                        self._running
                        and not self._urgent
                        and self._queued() < self._effective_max()
                    ):
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(remaining)
            # wait for an in-flight slot BEFORE packing (outside the
            # lock: submitters must keep filling the lanes meanwhile).
            # Packing as late as possible means a live/urgent arrival
            # during a full double buffer still rides the VERY NEXT
            # dispatch instead of waiting behind a pre-packed backfill
            # batch — one whole batch less of tail latency. Only this
            # thread pops the queues, so the batch cannot vanish between
            # the window wait and the pack.
            t_slot = time.monotonic()
            self._slots.acquire()
            with self._cv:
                batch, groups = self._pack_batch()
                if not self._queued():
                    self._urgent = False
                now = time.monotonic()
                self._stats["slot_wait_s"] += now - t_slot
                waited = 0.0
                # trace id -> [ctx, earliest join, requests, lane]: queue
                # and execute spans go out once per (dispatch, trace) — a
                # single vote's trace holds one request, a block-sync
                # range's thousands
                joined: dict[int, list] = {}

                def join(ctx, at, n, lane):
                    seen = joined.get(ctx.trace_id)
                    if seen is None:
                        joined[ctx.trace_id] = [ctx, at, n, lane]
                    else:
                        seen[1] = min(seen[1], at)
                        seen[2] += n

                for p in batch:
                    wait = now - p.enqueued_at
                    waited += wait
                    self.latency_hist.observe(wait)
                    if p.traces:
                        for ctx, at in p.traces:
                            join(ctx, at, 1, p.lane)
                sigs = len(batch)
                for g in groups:
                    # a group's wait weighs as its rows' would have
                    wait = now - g.enqueued_at
                    waited += wait * len(g.rows)
                    self.latency_hist.observe(wait, len(g.rows))
                    for ctx, at, n in g.traces:
                        join(ctx, at, n, g.lane)
                    sigs += len(g.rows)
                self._stats["queue_wait_s"] += waited
                # queue span: submit-to-pack wait, per joined trace.
                # enqueued_at is SYSTEM-domain; the trace may time on an
                # injected chaos clock, so measure the wait in SYSTEM and
                # anchor it ending at the trace clock's now (the reactor
                # does the same for p2p.receive)
                for ctx, at, n, lane in joined.values():
                    tc_now = ctx.clock.monotonic()
                    # tmtlint: allow[span-per-item] -- one row per (dispatch, trace), not per request
                    trace.record(
                        ctx, "hub", "queue",
                        tc_now - max(0.0, now - at), tc_now, lane=lane, n=n,
                    )
                self._stats["dispatches"] += 1
                self._stats["dispatched_sigs"] += sigs
                tenants: set = set()
                for p in batch:
                    if p.tenants:
                        tenants.update(p.tenants)
                if len(tenants) > 1:
                    # >1 verifyd client packed into ONE dispatch — the
                    # cross-process amortization the sidecar exists for
                    self._stats["cross_tenant_dispatches"] += 1
                alpha = 0.2
                self._ewma_occupancy = (
                    (1 - alpha) * self._ewma_occupancy + alpha * sigs
                )
            # hand off outside the lock; the runner's done-callback
            # frees the slot
            fut = self._runner.submit(self._run_batch, batch, groups, joined)
            fut.add_done_callback(lambda _f: self._slots.release())

    def _pack_batch(self) -> tuple[list[_Pending], list[_Group]]:
        """(lone requests, groups) of one dispatch. The lone requests
        first, up to the mesh-scaled batch capacity, live lane FIRST —
        catch-up traffic can never displace the hot path. Then groups,
        live lane first again: the first one WHOLE whatever its size — a
        group is never split, the verifier chunks what outgrows its
        program — and more only while the dispatch stays within the
        capacity (small commits still share a launch; a second range
        waits for the next one, and a live arrival rides ahead of it).
        Caller holds _cv."""
        cap = self._effective_max()
        batch: list[_Pending] = []
        for lane in LANES:
            q = self._queues[lane]
            while q and len(batch) < cap:
                _, p = q.popitem(last=False)
                self._inflight[p.key] = p
                batch.append(p)
                self._stats[f"lane_{lane}_dispatched"] += 1
        groups: list[_Group] = []
        sigs = len(batch)
        for lane in LANES:
            q = self._groups[lane]
            while q and (not groups or sigs + len(q[0].rows) <= cap):
                g = q.popleft()
                g.queued = False
                groups.append(g)
                sigs += len(g.rows)
                self._stats[f"lane_{lane}_dispatched"] += len(g.rows)
        return batch, groups

    def _run_batch(
        self, batch: list[_Pending], groups: list[_Group], joined: dict | None = None
    ) -> None:
        """Verify one packed dispatch on a runner thread — the lone
        requests, then each group's rows, as ONE batch — and settle it:
        one pass under the lock writes the verdicts into the LRU, clears
        the in-flight entries and fills each group's list in the caller's
        order. `joined` is the dispatcher's map of the traces the
        dispatch's requests belong to. This thread inherits no context:
        the hub.dispatch span joins the batch's trace when there is
        exactly one (a block-sync range: always), else stands alone,
        and lists every joined trace id."""
        self._worker_ids.add(threading.get_ident())
        joined = joined or {}
        only = next(iter(joined.values()))[0] if len(joined) == 1 else None
        rows = batch + [row for g in groups for row in g.rows] if groups else batch
        t0 = time.monotonic()
        with trace.span("hub", "dispatch", ctx=only, sigs=len(rows)) as sp:
            try:
                results = self._verify_batch(rows)
            except Exception as e:  # noqa: BLE001 — fail the batch, not the hub
                sp.set(error=repr(e))
                self._fail_batch(batch, groups, e)
                return
            # where THIS batch actually ran. _verify_batch stashed the
            # route in a thread-local: the process-global batch.LAST_ROUTE
            # can be overwritten by concurrent verifiers elsewhere (the
            # validation funnel builds its own)
            route = getattr(self._route_local, "route", "cpu")
            disp = getattr(self._route_local, "dispatch", None)
            host_rows = getattr(self._route_local, "host_rows", 0)
            sp.set(route=route)
            if host_rows:
                # rows that took the verifier's host lane (no batch kernel)
                sp.set(host_rows=host_rows)
            if joined:
                sp.set(traces=list(joined))
            if disp:
                # sharded dispatches carry per-device occupancy: device
                # ids + real signatures per shard (tracectl --per-device)
                sp.set(devices=disp["devices"], shards=disp["shards"])
        t1 = time.monotonic()
        futures: list[tuple[Future, bool]] = []
        with self._cv:
            cache = self._cache if self.cache_size else None
            for p, ok in zip(batch, results):
                self._inflight.pop(p.key, None)
                if cache is not None:
                    cache[p.key] = ok
                    cache.move_to_end(p.key)
                self._answer(p.futures, ok, futures)
            rest = iter(results[len(batch):])
            for g in groups:
                verdicts = g.results
                for row, ok in zip(g.rows, rest):
                    del self._group_rows[row.key]
                    if cache is not None:
                        cache[row.key] = ok
                    verdicts[row.index] = ok
                g.remaining -= len(g.rows)
                if not g.remaining:
                    g.wake()
                if g.joiners:
                    for row, waiters in g.joiners.items():
                        self._answer(waiters, verdicts[row.index], futures)
            if cache is not None:
                while len(cache) > self.cache_size:
                    cache.popitem(last=False)
        # t0/t1 are SYSTEM-domain; anchor each trace's execute span
        # ending at the trace clock's now so it sits correctly among the
        # trace's other spans under an injected chaos clock
        for ctx, _at, n, _lane in joined.values():
            tc_now = ctx.clock.monotonic()
            # tmtlint: allow[span-per-item] -- one row per (dispatch, trace), not per request
            trace.record(
                ctx, "hub", "execute", tc_now - (t1 - t0), tc_now,
                batch=len(rows), n=n, route=route,
            )
        for f, ok in futures:
            if not f.done():
                f.set_result(ok)

    @staticmethod
    def _answer(waiters: list, ok: bool, futures: list) -> None:
        """One verdict to whoever waits for it: a group's row is written
        in place (its caller woken with its last), a lone request's
        Future is noted for the caller to resolve once the lock is
        dropped. Caller holds _cv."""
        for w in waiters:
            if type(w) is tuple:
                group, i = w
                group.results[i] = ok
                group.remaining -= 1
                if not group.remaining:
                    group.wake()
            else:
                futures.append((w, ok))

    def _fail_batch(self, batch: list[_Pending], groups: list[_Group], e: Exception) -> None:
        waiters: list = []
        with self._cv:
            self._stats["verify_errors"] += 1
            for p in batch:
                self._inflight.pop(p.key, None)
                waiters.extend(p.futures)
            for g in groups:
                for row in g.rows:
                    del self._group_rows[row.key]
                g.fail(e)
                if g.joiners:
                    for others in g.joiners.values():
                        waiters.extend(others)
        logger.warning(
            "batch of %d failed to verify: %r", len(batch) + sum(len(g.rows) for g in groups), e
        )
        for w in waiters:
            if type(w) is tuple:
                # a row of another group rode this dispatch: that group fails
                w[0].fail(e)
            elif not w.done():
                w.set_exception(e)

    def _remote(self, purpose: str = "batch"):
        """The verifyd sidecar client for this hub's configured socket,
        or None when no remote route is configured. The client is
        process-wide (crypto/verifyd.client_for): every hub pointing at
        one socket shares one connection + breaker per purpose —
        aggregate checks get their own connection so a seconds-scale
        pairing round-trip never queues live vote batches behind it."""
        if not self.verifyd_sock:
            return None
        from . import verifyd

        return verifyd.client_for(self.verifyd_sock, purpose)

    def _verify_batch(self, batch: list[_Pending | _Row]) -> list[bool]:
        """One verifier call per dispatch, whatever its key types.

        Remote route first: when a verifyd sidecar is configured
        (`verifyd_sock`), the whole packed batch ships over the UDS and
        the daemon's hub re-batches it ACROSS client processes — the
        local cache/coalescing above already filtered everything warm,
        so the socket only carries cold batches. Any remote failure
        (breaker open, daemon busy, socket error) returns None from the
        client and the batch falls through to the local path below: the
        sidecar can never be a correctness or liveness event.

        Local path: a dispatch of ONE row verifies directly; every other
        goes to ONE AdaptiveBatchVerifier with every row in it, and the
        partition by scheme is the verifier's (crypto/batch): rows with
        no batch kernel (secp256k1) on its host lane — pool tasks started
        BEFORE the device partitions are routed and joined AFTER them,
        counted under their own route — BLS on the pairing path, the
        Edwards rows in one MSM dispatch; TPU/CPU routing, the breaker
        and the identical-result fallback live there too."""
        # rows by the verifier's own lanes (its rule, not a copy of it)
        edwards, host = rows_by_lane(Counter(p.pub_key.TYPE for p in batch))
        with self._cv:
            self._stats["scheme_edwards_sigs"] += edwards
            self._stats["scheme_bls_sigs"] += len(batch) - edwards - host
            self._stats["scheme_host_sigs"] += host
        # where this batch ran, for the dispatch/execute spans: set per
        # worker thread (concurrent _run_batch calls must not race)
        local = self._route_local
        local.route, local.dispatch, local.host_rows = "cpu", None, host
        remote = self._remote()
        if remote is not None:
            verdicts = remote.remote_verify_batch(
                [(p.pub_key, p.msg, p.sig, p.lane) for p in batch]
            )
            if verdicts is not None:
                # stamp the route for the hub.dispatch span: tracectl
                # can then attribute socket RTT vs local device time
                local.route = "verifyd"
                return verdicts
        if len(batch) == 1:
            p = batch[0]
            return [p.pub_key.verify_signature(p.msg, p.sig)]
        bv = AdaptiveBatchVerifier()
        if len(batch) > self._effective_max():
            # more rows than lone requests ever make: a group went out
            # whole. ONE device shape for every such size, the program
            # start-up warmed
            bv.whole_chunk = True
        bv.add_many([(p.pub_key, p.msg, p.sig) for p in batch])
        _ok, bitmap = bv.verify()
        # "mixed" where the verifier's partitions took different routes
        local.route, local.dispatch = bv.last_route, bv.last_dispatch
        return [bool(good) for good in bitmap]


# -- process-wide hub ------------------------------------------------------

_hub_lock = threading.Lock()
_default_hub: VerifyHub | None = None
_refs = 0


def acquire_hub(**kwargs) -> VerifyHub:
    """Refcounted access to the process-wide hub (node lifecycle). The
    first acquirer's config wins; in-process multi-node tests share one
    hub on purpose — cross-node gossip duplicates dedup too."""
    global _default_hub, _refs
    with _hub_lock:
        if _default_hub is None or not _default_hub.is_running:
            _default_hub = VerifyHub(**kwargs)
            _default_hub.start()
            logger.info(
                "verify hub started (max_batch=%d window=%.1fms cache=%d%s)",
                _default_hub.max_batch,
                _default_hub.window_s * 1e3,
                _default_hub.cache_size,
                f" verifyd={_default_hub.verifyd_sock}"
                if _default_hub.verifyd_sock
                else "",
            )
        _refs += 1
        return _default_hub


def release_hub() -> None:
    global _default_hub, _refs
    with _hub_lock:
        _refs = max(0, _refs - 1)
        if _refs == 0 and _default_hub is not None:
            _default_hub.stop()
            _default_hub = None


def running_hub() -> VerifyHub | None:
    """The process hub, or None when nothing acquired it (library use,
    unit tests) — callers then verify directly on the host."""
    hub = _default_hub
    return hub if hub is not None and hub.is_running else None


async def averify_one(
    pub_key: PubKey,
    msg: bytes,
    sig: bytes,
    *,
    lane: str = LANE_LIVE,
    trace_ctx=None,
) -> bool:
    """Async single-signature chokepoint (the coroutine-safe sibling of
    `verify_one`, used by the tx-ingress pipeline): awaits the batched
    verdict through the running hub — dedup cache + coalescing, zero
    event-loop blocking — and degrades to inline host verification when
    no hub is up or the hub errors, exactly like `verify_one`."""
    hub = running_hub()
    if hub is None:
        return pub_key.verify_signature(msg, sig)
    try:
        return await hub.verify(pub_key, msg, sig, lane=lane, trace_ctx=trace_ctx)
    except asyncio.CancelledError:
        raise
    except Exception as e:  # noqa: BLE001 — timeout/shutdown races
        logger.warning("hub verify failed (%r); verifying inline", e)
        return pub_key.verify_signature(msg, sig)


def aggregate_cache_key(pub_keys: list, msgs: list[bytes], agg_sig: bytes) -> tuple:
    """Verdict-LRU key for one aggregate-commit check. Shared with the
    verifyd daemon so both sides of the socket cache identically."""
    return (
        "bls-aggregate",
        sha256(
            b"".join(
                len(x).to_bytes(4, "big") + x
                for x in [pk.bytes() for pk in pub_keys] + [bytes(m) for m in msgs]
            )
        ),
        bytes(agg_sig),
    )


def verify_aggregate(pub_keys: list, msgs: list[bytes], agg_sig: bytes) -> bool:
    """THE aggregate-commit chokepoint (types/validation routes every
    aggregate `verify_commit*` here): one G2 aggregate signature
    checked against per-signer messages via a single pairing product.
    The check is indivisible — nothing to micro-batch — so it runs on
    the caller's thread through crypto/batch.bls_aggregate_verify
    (device routing + breaker + pure-Python fallback), but the running
    hub's verdict LRU still answers gossip re-verifications of the
    same commit without re-pairing. With a verifyd sidecar configured,
    a cache miss ships the check over the socket first (the daemon's
    warm pairing kernel + cross-client verdict cache); remote failure
    degrades to the local path like every other sidecar call."""
    key = aggregate_cache_key(pub_keys, msgs, agg_sig)
    hub = running_hub()
    if hub is not None:
        hit = hub.cached_verdict(key)
        if hit is not None:
            return hit
        remote = hub._remote("aggregate")
        if remote is not None:
            v = remote.remote_verify_aggregate(pub_keys, msgs, agg_sig)
            if v is not None:
                hub.store_verdict(key, v)
                return v
    from .batch import bls_aggregate_verify

    ok = bls_aggregate_verify(pub_keys, msgs, agg_sig)
    if hub is not None:
        hub.store_verdict(key, ok)
    return ok


def verify_one(
    pub_key: PubKey, msg: bytes, sig: bytes, *, lane: str = LANE_LIVE
) -> bool:
    """THE single-signature chokepoint (vote intake, proposal checks,
    evidence votes). Routes through the running hub — dedup cache +
    coalescing — and bypasses it when no hub is up. A hub stall or
    error degrades to inline host verification instead of leaking an
    exception into callers that expect a bool (a wedged hub must cost
    latency, never consensus-reactor crashes)."""
    hub = running_hub()
    if hub is None:
        return pub_key.verify_signature(msg, sig)
    try:
        return hub.verify_sync(pub_key, msg, sig, lane=lane)
    except Exception as e:  # noqa: BLE001 — timeout/shutdown races
        logger.warning("hub verify failed (%r); verifying inline", e)
        return pub_key.verify_signature(msg, sig)
