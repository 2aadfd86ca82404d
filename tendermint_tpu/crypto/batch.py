"""Batch-verifier dispatch (analog of reference crypto/batch/batch.go:11-31).

`create_batch_verifier(pubkey)` returns the best available batch verifier for
the key type: the TPU-backed JAX verifier for ed25519 when a TPU/accelerator
backend is usable, otherwise a CPU loop verifier. secp256k1 has no batch
kernel (matching the reference: `supports_batch_verifier` is False for it),
but the AdaptiveBatchVerifier does not refuse it: in a mixed batch its rows
take a host lane (`_HostLane`) that runs beside the device partitions.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import Counter
from operator import attrgetter, itemgetter

from ..libs import trace
from ..libs.metrics import record_resilience
from ..libs.retry import CircuitBreaker
from . import BatchVerifier, PubKey
from .bls import KEY_TYPE as BLS12381
from .ed25519 import KEY_TYPE as ED25519
from .secp256k1 import KEY_TYPE as SECP256K1
from .sr25519 import KEY_TYPE as SR25519

#: key types sharing the Edwards-curve MSM kernel (one TPU dispatch)
_EDWARDS = (ED25519, SR25519)
#: everything create_batch_verifier accepts; BLS batches through the
#: pairing kernel / pure-Python path, NEVER the Edwards kernel — the
#: AdaptiveBatchVerifier partitions by scheme so mixed validator sets
#: still funnel through one verifier object
_BATCHABLE = (ED25519, SR25519, BLS12381)
#: the route a key type WITHOUT a batch kernel is counted under
#: (`backend_telemetry.record_route`): its rows verify one by one on the
#: host lane, by design — never `cpu` (Edwards rows under the cut-off)
#: and never `cpu-fallback` (a host re-verify after a device error)
_HOST_LANE_ROUTES = {SECP256K1: "host-ecdsa"}
#: the key of a (pub_key, msg, sig) item and a key's scheme, for C-level
#: passes (`map`) over a handed-over list
_KEY_OF = itemgetter(0)
_SCHEME_OF = attrgetter("TYPE")

logger = logging.getLogger("crypto.batch")


class CPUBatchVerifier(BatchVerifier):
    """Verify each entry independently on the host. Large batches fan out
    over a thread pool — OpenSSL-backed ed25519 verification releases the
    GIL, so this scales with cores (the reference's Go verifier gets the
    same from goroutines). Small batches stay on the calling thread."""

    PARALLEL_THRESHOLD = 64

    def __init__(self, *, parallel: bool | None = None):
        self._items: list[tuple[PubKey, bytes, bytes]] = []
        self._parallel = parallel

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        self._items.append((pub_key, msg, sig))

    def verify(self) -> tuple[bool, list[bool]]:
        items = self._items
        use_threads = (
            self._parallel
            if self._parallel is not None
            else len(items) >= self.PARALLEL_THRESHOLD
        )
        if use_threads and len(items) > 1:
            results = list(_cpu_pool().map(_verify_one, items, chunksize=16))
        else:
            results = [_verify_one(it) for it in items]
        return all(results) and bool(results), results


def _verify_one(item: tuple[PubKey, bytes, bytes]) -> bool:
    pk, msg, sig = item
    return pk.verify_signature(msg, sig)


_pool = None
#: threads of the process's one host-verification pool (`_cpu_pool`): the
#: host route of a CPUBatchVerifier and the host lane of a mixed batch
_POOL_WIDTH = min(32, os.cpu_count() or 4)


def _cpu_pool():
    global _pool
    if _pool is None:
        from concurrent.futures import ThreadPoolExecutor

        _pool = ThreadPoolExecutor(max_workers=_POOL_WIDTH, thread_name_prefix="sigverify")
    return _pool


_tpu_available: bool | None = None
_tpu_probe_lock = threading.Lock()
_tpu_probe_started = False


def _probe_tpu() -> None:
    """Background probe: bring the JAX backend up, warm the kernel, and
    MEASURE the CPU/TPU crossover batch size so routing is based on this
    host's actual rates, not a guess. Every phase is recorded into
    `backend_telemetry` (attach latency, per-shape compile durations,
    the active verifier kind) so the attach story is readable from
    /metrics and trace dumps instead of log tails. The flight recorder
    gets the same story as one trace: `backend.probe` (root, to the
    thread's end; `available_s` is when routing could use the device)
    over `backend.attach`, `backend.warmup` [shape] — the first holds
    `backend.pallas_ab` [chosen, programs, stages], the Pallas self-test:
    each Pallas kernel held once to a known answer the host computes, all
    of it BEFORE the floor programs are traced and so before availability
    flips; a kernel that fails leaves the process on the XLA family,
    loudly, and the probe goes on with that — and `backend.cutoff`
    [value]."""
    with trace.span("backend", "probe", root=True) as sp:
        _probe_tpu_traced(sp)


def _probe_tpu_traced(probe_span) -> None:
    import time as _time

    global _tpu_available
    from . import backend_telemetry as bt

    attach_recorded = False
    t_start = _time.monotonic()
    try:
        from ..libs.watchdog import BackendInitWatchdog
        from .tpu.verify import backend_ready, warmup

        # watchdogged attach: bounded short attempts with a cheap poll
        # that adopts an earlier hung attempt finishing late (jax init
        # holds a global lock, so the thread can't be killed — only
        # outwaited). Each attempt lands in backend_telemetry; a device
        # that never answers costs bounded time before the host path
        # takes over instead of wedging the probe.
        wd = BackendInitWatchdog(
            attempts=int(os.environ.get("TMTPU_ATTACH_ATTEMPTS", "3")),
            timeout_s=float(os.environ.get("TMTPU_ATTACH_TIMEOUT", "60")),
            name="tpu-attach",
        )
        ok = bool(wd.run(backend_ready))
        attach_recorded = True
        if ok:
            # the JAX backend that actually answered: "tpu" only when a
            # device platform is behind it (a CPU-pinned process routes
            # the same kernels through the JAX-CPU backend and says so).
            # A backend that attached but cannot name its platform is an
            # error the outer handler records, not a label.
            import jax

            from .tpu.verify import _shard_device_count

            platform = jax.devices()[0].platform
            # mesh telemetry: record the topology the moment the attach
            # succeeds, before any warmup can hang. active honors
            # TMTPU_NO_SHARDED / MAX_DEVICES — the DISPATCH mesh, not
            # the raw device count
            bt.record_mesh(len(jax.devices()), _shard_device_count())
            bt.set_active("cpu" if platform == "cpu" else "tpu")
            # fallback=True also compiles the per-signature attribution
            # kernel: the first bad signature in a gossiped batch must not
            # stall verification behind an inline JIT compile. groups=150
            # warms the grouped A-side at the bucket a realistic validator
            # set lands on (gb=255), not just the all-padding floor shape
            t0 = _time.monotonic()
            with trace.span("backend", "warmup", shape="floor"):
                warmup(groups=150, fallback=True)
            bt.record_compile("floor", _time.monotonic() - t0)
            with trace.span("backend", "cutoff") as sp:
                _measure_cutoff()
                sp.set(value=MIN_TPU_BATCH)
        # the TPU is usable as soon as the floor shapes are warm — flip
        # availability BEFORE the optional big-bucket warm below, so
        # normal consensus batches aren't CPU-routed for the minutes a
        # cold 8192-shape compile can take
        _tpu_available = ok
        probe_span.set(ok=ok, available_s=round(_time.monotonic() - t_start, 3))
        if not ok:
            bt.set_active("cpu")
        logger.info("TPU batch verifier %s", "ready" if ok else "unavailable")
        if ok:
            # pre-compile the block-sync range shape too (still on the
            # background thread, both the batch-equation kernel and the
            # bad-batch attribution fallback): the first historical-sync
            # chunk otherwise stalls inline on a multi-minute XLA compile.
            # Its failure does NOT revoke availability — the floor shapes
            # are warm and perfectly usable — but it is counted
            # (backend_telemetry probe_errors), not just logged.
            from .tpu.verify import _CHUNK_GROUPS, _MAX_BUCKET

            try:
                t0 = _time.monotonic()
                with trace.span("backend", "warmup", shape="max"):
                    warmup(bucket=_MAX_BUCKET, groups=_CHUNK_GROUPS, fallback=True)
                bt.record_compile("max", _time.monotonic() - t0)
            except Exception as e:  # noqa: BLE001
                bt.record_probe_error("warmup-max", repr(e))
    except Exception as e:
        logger.warning("TPU batch verifier unavailable: %r", e)
        if attach_recorded:
            # the backend attached and a later step (platform read,
            # floor warmup, cutoff measurement) raised: not a second
            # attach attempt, but an error the probe records
            bt.record_probe_error("probe", repr(e))
        else:
            # import/infra failure before the watchdog ran
            bt.record_attach_attempt(0.0, False, error=repr(e))
        bt.set_active("cpu")
        _tpu_available = False


#: distinct keys in the cutoff probe batch = the validator-set size the
#: floor warmup compiled for (warmup(groups=150) in _probe_tpu)
_WARM_GROUPS = 150


def _measure_cutoff() -> None:
    """Derive MIN_TPU_BATCH from measurement (runs once, after warmup):
    time one WARMED device call at the floor shape (fixed overhead
    dominates there) and the parallel host verifier on the same batch;
    route to the device from the size where its flat call cost beats the
    host's per-signature rate. The probe batch fills the floor-warm
    bucket with signatures from _WARM_GROUPS distinct keys — exactly the
    (selection, bucket, key-group) shape the floor warmup just compiled;
    any other shape would time an inline cold compile and pin the cutoff
    at its ceiling. Honors
    TMTPU_MIN_TPU_BATCH as an override."""
    global MIN_TPU_BATCH
    if os.environ.get("TMTPU_MIN_TPU_BATCH"):
        return
    import time

    from .ed25519 import Ed25519PrivKey
    from .tpu.verify import _bucket, verify_batch_eq

    keys = [
        Ed25519PrivKey(b"cutoff-probe" + i.to_bytes(4, "big") + b"\x42" * 16)
        for i in range(_WARM_GROUPS)
    ]
    items = []
    for i in range(_bucket(_WARM_GROUPS)):  # a full floor-warm bucket
        priv = keys[i % _WARM_GROUPS]
        msg = b"cutoff-probe-%d" % i
        items.append((priv.pub_key(), msg, priv.sign(msg)))
    raw = [(pub.bytes(), msg, sig) for pub, msg, sig in items]
    t0 = time.perf_counter()
    verify_batch_eq(raw)
    tpu_call_s = time.perf_counter() - t0

    for _ in range(2):  # warm the pool, then measure
        bv = CPUBatchVerifier(parallel=True)
        for pub, msg, sig in items:
            bv.add(pub, msg, sig)
        t0 = time.perf_counter()
        bv.verify()
        cpu_s = time.perf_counter() - t0
    cpu_rate = len(items) / max(cpu_s, 1e-9)
    measured = int(tpu_call_s * cpu_rate) + 1
    MIN_TPU_BATCH = max(8, min(2048, measured))
    logger.info(
        "measured TPU cutoff: device call %.2fms, host %.0f sigs/s -> "
        "MIN_TPU_BATCH=%d",
        tpu_call_s * 1e3,
        cpu_rate,
        MIN_TPU_BATCH,
    )


def tpu_verifier_available() -> bool:
    """True when the JAX backend is up AND the kernel is warmed.

    Backend init + first compile can take minutes (large kernels, a
    cold compile cache), so the probe runs on a daemon thread and this returns False
    — routing batches to the host verifier — until it finishes. NEVER
    blocks (coroutines call it to kick the probe: the tmtlint
    transitive-blocking pass holds this structurally — the wait loop
    lives in `tpu_wait_available`, which no async path may reach).
    Disable with TMTPU_DISABLE_TPU=1."""
    global _tpu_probe_started
    if _tpu_available is not None:
        return _tpu_available
    if os.environ.get("TMTPU_DISABLE_TPU"):
        return False
    with _tpu_probe_lock:
        if not _tpu_probe_started:
            _tpu_probe_started = True
            t = threading.Thread(target=_probe_tpu, name="tpu-probe", daemon=True)
            t.start()
    return False if _tpu_available is None else _tpu_available


def tpu_wait_available() -> bool:
    """Blocking companion of `tpu_verifier_available`: kick the probe
    and WAIT for its verdict. Benchmarks/tools only — never call from
    a coroutine (or anything a coroutine calls)."""
    tpu_verifier_available()  # start the probe thread if needed
    if os.environ.get("TMTPU_DISABLE_TPU") and _tpu_available is None:
        return False
    import time

    # always re-read the global: the probe may land between the kick
    # above and here, and this function's contract is the FINAL verdict
    while _tpu_available is None:
        time.sleep(0.1)
    return _tpu_available


# Below this many signatures the TPU round-trip (host transfer + launch
# overhead) costs more than it saves — verify on the host instead. This
# initial value is replaced by a MEASURED crossover in _measure_cutoff()
# when the device probe completes (SURVEY.md §7 hard-part #2);
# TMTPU_MIN_TPU_BATCH pins it explicitly.
MIN_TPU_BATCH = int(os.environ.get("TMTPU_MIN_TPU_BATCH", "32"))

#: where the most recent adaptive batch actually executed ("tpu",
#: "cpu", or "cpu-fallback" after a device error). Diagnostics only —
#: the VerifyHub stamps it on dispatch spans so a trace dump shows
#: which backend served each batch. (The hub's REMOTE route stamps
#: "verifyd" on its spans directly — a batch shipped to the sidecar
#: daemon never reaches this module in the client process; the
#: daemon's own hub records the device route on ITS spans.)
LAST_ROUTE = "cpu"


# TPU-path circuit breaker: any backend/kernel error mid-batch trips it
# (the batch transparently re-verifies on the CPU — results are identical,
# only slower), routing stays on the host while it is open, and a
# half-open probe periodically re-tries the device. One failure is enough
# to trip: a crashed backend keeps failing, and 30 s of host routing is
# cheap next to a stalled sync pipeline. Env overrides for ops/tests.
_tpu_breaker = CircuitBreaker(
    failure_threshold=int(os.environ.get("TMTPU_TPU_BREAKER_THRESHOLD", "1")),
    reset_timeout=float(os.environ.get("TMTPU_TPU_BREAKER_RESET", "30")),
    name="tpu-batch-verify",
)


def tpu_breaker() -> CircuitBreaker:
    """The process-wide TPU-path breaker (exposed for tests/ops)."""
    return _tpu_breaker


def mesh_parallelism() -> int:
    """Active device count sharded dispatch can use right now: 1 until
    the backend probe completes, when sharding is disabled, or when only
    one chip is healthy. The VerifyHub scales its micro-batch window and
    capacity by this so an 8-chip mesh is fed 8-chip-sized batches —
    and shrinks back automatically when per-device breakers degrade the
    mesh. Cheap when no accelerator is up (no jax import)."""
    if not _tpu_available:
        return 1
    try:
        from .tpu.verify import _shard_device_count

        return max(1, _shard_device_count())
    except Exception:  # noqa: BLE001 — diagnostics must not break dispatch
        return 1


class AdaptiveBatchVerifier(BatchVerifier):
    """Collects entries, PARTITIONS them by scheme (Edwards vs BLS — the
    two never share a kernel dispatch), and routes each partition to its
    device kernel when it is large enough (and a backend is usable),
    else verifies on the host. Small commits therefore never pay a
    device round-trip or a first-call compile.

    Rows of a key type that has NO batch kernel (secp256k1) are a third
    partition, the host lane (`_HostLane`): `verify_signature` a row on
    the process's `_cpu_pool()`, started BEFORE the device partitions
    are routed and joined AFTER them, so it runs under their resolve,
    prep, dispatch and wait and not behind them. A mixed validator set
    therefore keeps its Edwards rows in one batch on the device;
    verdicts land in the caller's order whichever lane gave them.

    Degradation: a device failure mid-batch (backend crash, kernel
    error) re-verifies the SAME partition on the CPU path — the caller
    sees the identical (ok, per-signature) result, never the error —
    trips the shared TPU circuit breaker, and records the event in
    libs/metrics. While the breaker is open all batches route to the
    host; its half-open probe sends one batch back to the device to
    test recovery. The BLS pairing kernel sits behind the SAME breaker:
    a sick backend degrades both schemes at once, which is correct —
    they share the device."""

    def __init__(self):
        self._items: list[tuple[PubKey, bytes, bytes]] = []
        #: key types seen so far: says whether there is anything to
        #: partition without a pass over the items
        self._schemes: set[str] = set()
        #: where the last verify() ran ("tpu"/"cpu"/"cpu-fallback", or
        #: "mixed" when scheme partitions took different routes) —
        #: per-instance, unlike the process-global LAST_ROUTE, so
        #: concurrent verifiers can't misattribute each other's batches
        self.last_route = "cpu"
        #: {devices: [...], shards: [...]} when the last verify ran
        #: sharded over the mesh (per-device real-signature counts);
        #: None on single-device and host routes
        self.last_dispatch = None
        #: the device verifier runs this batch at its full chunk shape —
        #: the program start-up warms last (`_probe_tpu`) — whatever its
        #: row count, instead of the ladder rung: set by the VerifyHub on
        #: a dispatch that carries a whole group, whose size is whatever
        #: a catch-up happened to download (one program for every such
        #: size, none compiled in the middle of a sync)
        self.whole_chunk = False

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        self._schemes.add(pub_key.TYPE)
        self._items.append((pub_key, msg, sig))

    def add_many(self, items: list[tuple[PubKey, bytes, bytes]]) -> None:
        """Bulk hand-over beside the reference's `add`: a whole list of
        (pub_key, msg, sig) in one step — one C-level pass for the key
        types, one list extend — instead of a call a signature."""
        self._schemes.update(map(_SCHEME_OF, map(_KEY_OF, items)))
        self._items.extend(items)

    def verify(self) -> tuple[bool, list[bool]]:
        global LAST_ROUTE
        from . import backend_telemetry as bt

        items = self._items
        self.last_dispatch = None
        if self._schemes.issubset(_EDWARDS):
            # every key is an Edwards key (every commit of an ed25519
            # chain): nothing to partition — the list goes on as it is,
            # and the route's verdicts are the answer
            results, route = [], "cpu"
            if items:
                results, route = self._verify_edwards(items, partitions=1)
                bt.record_route(route, len(items))
            LAST_ROUTE = self.last_route = route
            return all(results) and bool(results), results
        results = [False] * len(items)
        edwards, bls, host = [], [], []
        for i, it in enumerate(items):
            scheme = it[0].TYPE
            (edwards if scheme in _EDWARDS else bls if scheme == BLS12381 else host).append(i)
        partitions = bool(edwards) + bool(bls) + bool(host)
        # the host lane first: it runs on the pool while this thread
        # routes, prepares, dispatches and waits for the device partitions
        lane = _HostLane([items[i] for i in host]) if host else None
        routes = []

        def settle(idxs, verdicts, route):
            for i, ok in zip(idxs, verdicts):
                results[i] = ok
            bt.record_route(route, len(idxs))
            routes.append(route)

        if bls:
            settle(bls, *self._verify_bls([items[i] for i in bls]))
        if edwards:
            settle(edwards, *self._verify_edwards([items[i] for i in edwards], partitions))
        if lane is not None:
            for i, ok in zip(host, lane.join()):
                results[i] = ok
            routes.extend(lane.routes)
        LAST_ROUTE = self.last_route = routes[0] if len(set(routes)) == 1 else "mixed"
        return all(results) and bool(results), results

    def _verify_edwards(self, items, partitions: int) -> tuple[list[bool], str]:
        """The ed25519/sr25519 partition: shared-MSM TPU kernel when the
        batch clears the measured cutoff, host loop otherwise.
        `partitions` goes on the span: 1 = the verifier's list went
        through whole, else the parts it was split into by scheme (the
        Edwards rows, a BLS part, the host lane: each counts one)."""
        with trace.span(
            "batch", "route", n=len(items), cutoff=MIN_TPU_BATCH, partitions=partitions
        ) as sp:
            results, route, why = self._route_edwards(items)
            sp.set(route=route, **({"why": why} if why else {}))
        return results, route

    def _route_edwards(self, items) -> tuple[list[bool], str, str]:
        """(verdicts, route, why the host served it — "" on the device)."""
        if len(items) < MIN_TPU_BATCH:
            why = "below-cutoff"
        elif not tpu_verifier_available():
            why = "no-device"
        else:
            out = self._device_guarded(lambda: self._run_device(items), len(items))
            if out is _DEVICE_FAILED:
                return self._run(CPUBatchVerifier(), items)[1], "cpu-fallback", "device-error"
            if out is not None:
                from .tpu.verify import last_dispatch_info

                self.last_dispatch = last_dispatch_info()
                return out[1], "tpu", ""
            why = "breaker-open"
        return self._run(CPUBatchVerifier(), items)[1], "cpu", why

    def _verify_bls(self, items) -> tuple[list[bool], str]:
        """The BLS partition: the batched pairing-product kernel when
        the opt-in device path is enabled (TMTPU_BLS_TPU=1 — a cold
        pairing compile is minutes-scale, so it never engages
        implicitly), pure-Python verification otherwise. Same breaker,
        same identical-result CPU re-verify on device failure."""
        from .tpu import bls_pairing

        if len(items) >= 2 and bls_pairing.device_enabled():
            out = self._device_guarded(
                lambda: (True, self._run_bls_kernel(items)), len(items)
            )
            if out is not None:
                if out is not _DEVICE_FAILED:
                    return out[1], "tpu"
                return [
                    pk.verify_signature(msg, sig) for pk, msg, sig in items
                ], "cpu-fallback"
        return [pk.verify_signature(msg, sig) for pk, msg, sig in items], "cpu"

    def _run_bls_kernel(self, items) -> list[bool]:
        """Host prep + batched pairing kernel: decode/subgroup-check
        through the bls point caches; undecodable entries are False
        without costing a kernel slot."""
        from . import bls as bls_keys
        from .tpu import bls_pairing

        results = [False] * len(items)
        triples = []
        idxs = []
        for i, (pk, msg, sig) in enumerate(items):
            if len(sig) != bls_keys.SIGNATURE_SIZE:
                continue
            pt = bls_keys.pubkey_point(pk.bytes())
            sp = bls_keys.signature_point(bytes(sig))
            if pt is None or sp is None:
                continue
            triples.append((pt, msg, sp))
            idxs.append(i)
        if triples:
            ok = bls_pairing.verify_items(triples)
            for i, good in zip(idxs, ok):
                results[i] = bool(good)
        return results

    def _device_guarded(self, run, n_sigs: int):
        return _device_guarded(run, n_sigs)

    def _make_tpu_verifier(self) -> BatchVerifier:
        from .tpu.verify import TPUBatchVerifier

        return TPUBatchVerifier()

    def _run(self, target: BatchVerifier, items=None) -> tuple[bool, list[bool]]:
        for pk, msg, sig in items if items is not None else self._items:
            target.add(pk, msg, sig)
        return target.verify()

    def _run_device(self, items) -> tuple[bool, list[bool]]:
        """`_run` on the device verifier: the list is handed over in one
        step where the verifier takes one (`add_many`); resolving — the
        SHA-512 per signature — is the verifier's own, chunk by chunk
        inside its dispatch loop (`tpu.resolve` spans are recorded
        there)."""
        target = self._make_tpu_verifier()
        if self.whole_chunk:
            target.whole_chunk = True
        add_many = getattr(target, "add_many", None)
        if add_many is None:
            return self._run(target, items)
        add_many(items)
        return target.verify()


#: rows to a pool task of the host lane: the lane is cut into tasks this
#: long and the pool's threads take them as they come free, so a thread on
#: a slower core takes fewer. secp256k1 verification drops the GIL for the
#: length of its OpenSSL call (`crypto/secp256k1.py`), so the threads run
#: side by side: read on the chip machine's 13-core host (PERF.md §6, PR
#: 32), 6,528 rows take 2.97 s on one thread and 0.37 s on thirteen (0.42
#: in thirteen equal slices). 64 rows are some 30 ms of one core against
#: some 50 us to hand a task over
_HOST_LANE_TASK_ROWS = 64


def _verify_slice(items) -> list[bool]:
    return [pk.verify_signature(msg, sig) for pk, msg, sig in items]


class _HostLane:
    """The rows of a batch whose key type has no batch kernel, verified
    one by one (`pub_key.verify_signature`) on `_cpu_pool()`, in tasks of
    `_HOST_LANE_TASK_ROWS` rows over the pool's width: started when made,
    joined by `join()` once the caller has served the device partitions.
    Leaves two rows in the flight recorder: `batch.host_lane`
    [n, scheme, workers], from the lane's start to the end of its join,
    and under it `batch.host_lane_wait` [n], the part of the join the
    caller spent blocked — what the overlap did not hide. Counts its
    rows under their scheme's own route (`_HOST_LANE_ROUTES`)."""

    def __init__(self, items):
        self._items = items
        step = _HOST_LANE_TASK_ROWS
        self._t0 = time.monotonic()
        pool = _cpu_pool()
        self._futs = [
            pool.submit(_verify_slice, items[i : i + step]) for i in range(0, len(items), step)
        ]
        self._workers = min(_POOL_WIDTH, len(self._futs))
        self.routes: list[str] = []

    def join(self) -> list[bool]:
        from . import backend_telemetry as bt

        n = len(self._items)
        with trace.span("batch", "host_lane_wait", n=n):
            verdicts = [ok for f in self._futs for ok in f.result()]
        by_scheme = Counter(map(_SCHEME_OF, map(_KEY_OF, self._items)))
        for scheme, rows in by_scheme.items():
            route = _HOST_LANE_ROUTES.get(scheme, f"host-{scheme}")
            bt.record_route(route, rows)
            self.routes.append(route)
        trace.emit(
            "batch", "host_lane", duration_s=time.monotonic() - self._t0,
            n=n, scheme="+".join(sorted(by_scheme)), workers=self._workers,
        )
        return verdicts


#: sentinel distinguishing "device attempt failed (breaker tripped)"
#: from "breaker already open" in _device_guarded
_DEVICE_FAILED = object()


def _device_guarded(run, n_sigs: int):
    """Run a device attempt behind the shared TPU breaker. Returns the
    run's result, _DEVICE_FAILED after a recorded device error (caller
    re-verifies on CPU), or None when the open breaker kept us off the
    device entirely."""
    probing = _tpu_breaker.state != "closed"  # read before allow() claims
    if not _tpu_breaker.allow():
        return None
    from . import backend_telemetry as bt

    if probing:
        record_resilience("tpu_breaker_probes")
        bt.record_breaker("half-open")
        logger.info("TPU breaker half-open: probing the device path")
    try:
        out = run()
    except Exception as e:  # noqa: BLE001 — any device error degrades
        opens_before = _tpu_breaker.opens
        _tpu_breaker.record_failure()
        record_resilience("tpu_fallback_batches")
        record_resilience("tpu_fallback_sigs", n_sigs)
        if _tpu_breaker.opens > opens_before:
            record_resilience("tpu_breaker_opens")
            bt.record_breaker("open")
        bt.record_fallback("tpu", "cpu", repr(e))
        logger.warning(
            "device batch verification failed (%r); re-verifying "
            "%d signatures on CPU (breaker %s)",
            e,
            n_sigs,
            _tpu_breaker.state,
        )
        return _DEVICE_FAILED
    if probing:
        bt.record_breaker("closed")
        bt.set_active("tpu")
    _tpu_breaker.record_success()
    return out


def bls_aggregate_verify(pub_keys: list, msgs: list[bytes], agg_sig: bytes) -> bool:
    """Aggregate-commit verification with device routing: the whole
    check is ONE multi-pair pairing-product item, so it rides the BLS
    kernel as a single dispatch when the opt-in device path is enabled
    (same breaker / identical-result CPU fallback as batched verifies)
    and the pure-Python path otherwise. Callers outside crypto/ go
    through crypto/verify_hub.verify_aggregate (verdict cache)."""
    from . import bls
    from .tpu import bls_pairing

    if bls_pairing.device_enabled():
        from . import bls_math

        agg = bls.signature_point(bytes(agg_sig)) if len(agg_sig) == bls.SIGNATURE_SIZE else None
        pts = [bls.pubkey_point(pk.bytes()) if getattr(pk, "TYPE", None) == bls.KEY_TYPE else None for pk in pub_keys]
        if agg is None or not pts or len(pts) != len(msgs) or any(p is None for p in pts):
            # same reject surface AND same counters as the pure path —
            # the bls_* metrics must not read zero on exactly the
            # deployments that enable the kernel route
            bls.STATS["aggregate_verifies"] += 1
            bls.STATS["aggregate_signers"] += len(pub_keys)
            bls.STATS["aggregate_failures"] += 1
            return False
        item = [(bls_math.NEG_G1_GEN, agg)] + [
            (pt, bls_math.hash_to_point_g2(bytes(m))) for pt, m in zip(pts, msgs)
        ]

        def run():
            return bls_pairing.verify_pairs_batch(
                [item],
                pad_to=bls_pairing.bucket_items(1),
                pair_pad=bls_pairing.bucket_pairs(len(item)),
            )

        out = _device_guarded(run, len(pub_keys))
        if out is not None and out is not _DEVICE_FAILED:
            ok = bool(out[0])
            bls.STATS["aggregate_verifies"] += 1
            bls.STATS["aggregate_signers"] += len(pub_keys)
            if not ok:
                bls.STATS["aggregate_failures"] += 1
            return ok
    return bls.aggregate_verify(pub_keys, msgs, agg_sig)


def supports_batch_verifier(pub_key: PubKey) -> bool:
    """ed25519 and sr25519 batch through the Edwards MSM kernel
    (reference crypto/batch/batch.go:26 — same two types); bls12381
    batches through the pairing kernel / pure-Python path. secp256k1
    has no batch kernel: False here, as in the reference — its rows in
    an AdaptiveBatchVerifier take the host lane."""
    return pub_key.TYPE in _BATCHABLE


def rows_by_lane(rows_by_scheme: dict[str, int]) -> tuple[int, int]:
    """(Edwards rows, host-lane rows) of a count of rows by key type —
    what the commit funnel notes on `validation.collect`."""
    edwards = sum(n for scheme, n in rows_by_scheme.items() if scheme in _EDWARDS)
    host = sum(n for scheme, n in rows_by_scheme.items() if scheme not in _BATCHABLE)
    return edwards, host


def create_batch_verifier(pub_key: PubKey) -> BatchVerifier:
    """The batch verifier for a key type that has a batch kernel (the
    reference's CreateBatchVerifier: a key type without one is refused
    HERE; the verifier itself takes such rows beside the others)."""
    if pub_key.TYPE in _BATCHABLE:
        return AdaptiveBatchVerifier()
    raise ValueError(f"key type {pub_key.TYPE!r} does not support batch verification")
