"""Backend-attach telemetry — the accelerator's black box recorder.

A device that did not come up, or came up and was then worked around,
must be readable from outside the process: the production path
re-verifies on the host after any device error (a guarantee), so the
caller's bitmap alone cannot tell a healthy chip from a dead one. This
module makes every attach-path event a first-class signal: attach
attempts (latency + outcome), XLA compile/warmup durations per shape
bucket, which backend served each batch, every host re-verify after a
device error, mesh degrade-and-retry events, Pallas self-test failures and
circuit-breaker state changes all land

  * in the module-level stores below (folded into `/metrics` at render
    time by `libs/metrics.NodeMetrics`, exactly like RESILIENCE and
    STORAGE — crypto backends are process-wide, not per-node), and
  * in the flight recorder (`libs/trace.py`) as ``backend.*`` spans, so
    a trace dump shows WHEN the device came up relative to the traffic
    that needed it.

Metric families rendered from here: ``backend_attach_attempts``,
``backend_attach_latency_seconds`` (histogram),
``backend_compile_seconds{shape=}``, ``backend_active{kind=}``,
``backend_fallbacks``, ``backend_breaker_transitions``,
``backend_compile_cache_hits``/``_misses``,
``backend_mesh_devices{state=}``, ``backend_mesh_degrades``,
``backend_shard_sigs{device=}``.

Mesh telemetry: device count at attach, per-device shard occupancy of
every sharded dispatch, and every degrade/recover transition of the
per-device breakers land here — a mesh losing a chip is a structured
record with a flight dump, not a timeout with no artifact.

Writers: `crypto/batch.py` (probe — attach runs behind
`libs/watchdog.BackendInitWatchdog` — warmup, routes, breaker,
fallback), `crypto/tpu/verify.py` (Pallas self-test, degrade-and-retry).
Readers besides /metrics: `chip_smoke.py` asserts on these stores that
the device, not a fallback, served its batches.
"""

from __future__ import annotations

import logging

from ..libs import trace

logger = logging.getLogger("crypto.backend_telemetry")

#: attach-latency buckets (seconds): init ranges from sub-second (warm
#: CPU) to minutes (a device that is slow to answer)
ATTACH_BUCKETS = (0.1, 0.5, 1, 5, 10, 30, 60, 120, 180, 300)

#: counters folded into /metrics at render time
BACKEND: dict[str, float] = {
    "attach_attempts": 0.0,   # init attempts (success or not)
    "attach_failures": 0.0,   # attempts that raised or timed out
    "fallbacks": 0.0,         # TPU->CPU fallback EVENTS (per failed batch)
    "breaker_transitions": 0.0,  # breaker open/half-open/close events
    "compile_cache_hits": 0.0,   # persistent-cache warm compiles (~0 ms)
    "compile_cache_misses": 0.0,  # cold XLA compiles that hit the disk cache
    "probe_errors": 0.0,      # probe steps that raised after a good attach
    "pallas_probe_errors": 0.0,  # Pallas kernels that failed/mismatched on a TPU
    "degrade_retries": 0.0,   # sharded chunks re-dispatched on a degraded mesh
}

#: where batches actually ran, counted per AdaptiveBatchVerifier partition:
#: route ("tpu" | "cpu" | "cpu-fallback") -> [batches, signatures]. "cpu"
#: is the by-design host route (below the cutoff, or no device);
#: "cpu-fallback" is a host re-verify after a device error.
ROUTES: dict[str, list[float]] = {}

#: a "compile" that finishes under this is a persistent-cache
#: deserialize, not a compile: jax only persists compilations that took
#: ≥ jax_persistent_cache_min_compile_time_secs (1.0 s, set in
#: crypto/tpu/verify._ensure_compile_cache), so a warm-cache load of any
#: cached kernel lands well under the same line
COMPILE_CACHE_HIT_S = 1.0

#: mesh state (multi-chip sharded dispatch): device counts + degrade
#: transitions of the per-device breakers (crypto/tpu/mesh.py)
MESH: dict[str, float] = {
    "devices_total": 0.0,     # devices visible at attach
    "devices_active": 0.0,    # devices currently in the dispatch mesh
    "degrade_transitions": 0.0,  # mesh membership changes (either way)
}

#: device id -> signatures dispatched to that device's shard (real rows
#: only, padding excluded) — the per-device occupancy record
SHARD_SIGS: dict[str, float] = {}
SHARD_DISPATCHES: dict[str, float] = {}

#: shape bucket -> "hit"/"miss" of the last compile (persistent cache)
COMPILE_CACHE: dict[str, str] = {}

#: per-attempt latency observations (seconds) — rendered as the
#: backend_attach_latency_seconds histogram; bounded so a flapping
#: device cannot grow it without limit
ATTACH_LATENCIES: list[float] = []
_MAX_LATENCIES = 512

#: shape bucket -> last compile/warmup duration (seconds)
COMPILE_SECONDS: dict[str, float] = {}

#: which verifier the process is actually using right now
ACTIVE: dict[str, str] = {"kind": "none"}  # "tpu" | "cpu" | "none"


def record_attach_attempt(
    latency_s: float, ok: bool, *, kind: str = "", error: str = ""
) -> None:
    """One backend-init attempt finished (or timed out). `kind` is the
    platform that came up ("tpu"/"cpu"/the jax platform name)."""
    BACKEND["attach_attempts"] += 1
    if not ok:
        BACKEND["attach_failures"] += 1
    if len(ATTACH_LATENCIES) < _MAX_LATENCIES:
        ATTACH_LATENCIES.append(latency_s)
    trace.emit(
        "backend",
        "attach",
        duration_s=latency_s,
        ok=ok,
        kind=kind or "unknown",
        **({"error": error} if error else {}),
    )
    if ok and kind:
        set_active(kind)
    logger.info(
        "backend attach attempt: %s in %.2fs%s",
        "up" if ok else "FAILED",
        latency_s,
        f" ({kind})" if kind else (f" ({error})" if error else ""),
    )


def record_compile(shape: str, seconds: float, *, cache_hit: bool | None = None) -> None:
    """An XLA compile/warmup finished for one shape bucket (the floor
    chunk, the blocksync max bucket, the fallback kernel, …). Classifies
    the persistent compile cache outcome: compile_ms ≈ 0 means the disk
    cache answered (deserialize), anything slower was a cold compile —
    the ROADMAP's 20–83 s warmup cliffs become countable."""
    COMPILE_SECONDS[shape] = seconds
    if cache_hit is None:
        cache_hit = seconds < COMPILE_CACHE_HIT_S
    COMPILE_CACHE[shape] = "hit" if cache_hit else "miss"
    BACKEND["compile_cache_hits" if cache_hit else "compile_cache_misses"] += 1
    trace.emit(
        "backend", "compile", duration_s=seconds, shape=shape,
        cache="hit" if cache_hit else "miss",
    )


def record_mesh(total: int, active: int) -> None:
    """The device mesh attached (or was re-read): how many chips are
    visible and how many are in the active dispatch set."""
    MESH["devices_total"] = float(total)
    MESH["devices_active"] = float(active)
    trace.emit("backend", "mesh", devices_total=total, devices_active=active)
    logger.info("device mesh: %d device(s), %d active", total, active)


def record_degrade(from_n: int, to_n: int, reason: str) -> None:
    """Mesh membership changed: a per-device breaker tripped (to_n <
    from_n) or a recovery probe re-admitted a chip (to_n > from_n).
    Each transition dumps the flight ring — degrades are rare and each
    one is a hardware event worth its own artifact."""
    MESH["degrade_transitions"] += 1
    MESH["devices_active"] = float(to_n)
    trace.emit(
        "backend", "mesh_degrade",
        from_devices=from_n, to_devices=to_n, reason=reason,
    )
    if to_n < from_n:
        logger.warning(
            "mesh degraded %d -> %d device(s): %s", from_n, to_n, reason
        )
        trace.auto_dump("mesh-degrade")
    else:
        logger.info("mesh recovered %d -> %d device(s)", from_n, to_n)


def record_shard_dispatch(device_ids, shard_fill) -> None:
    """One sharded dispatch landed: per-device real-signature counts
    (padding rows excluded) keyed by device id — into SHARD_SIGS, and
    into the flight recorder as one `tpu.shard.<id>` [n] event a device,
    so a reader of a window sees which chip carried what inside it."""
    for dev_id, n in zip(device_ids, shard_fill):
        key = str(dev_id)
        SHARD_SIGS[key] = SHARD_SIGS.get(key, 0.0) + float(n)
        SHARD_DISPATCHES[key] = SHARD_DISPATCHES.get(key, 0.0) + 1.0
        # tmtlint: allow[span-per-item] -- per device of a mesh, once a chunk
        trace.emit("tpu", f"shard.{key}", n=int(n))


def record_route(route: str, n_sigs: int) -> None:
    """One batch partition was served by `route`."""
    c = ROUTES.setdefault(route, [0.0, 0.0])
    c[0] += 1
    c[1] += n_sigs


def record_probe_error(stage: str, error: str) -> None:
    """A step of the device probe raised AFTER the backend attached
    (platform read, warmup, cutoff measurement). The probe's verdict
    says what that costs; this makes the event itself countable."""
    BACKEND["probe_errors"] += 1
    trace.emit("backend", "probe_error", stage=stage, error=error)
    logger.warning("device probe step %r failed: %s", stage, error)


def record_fallback(from_kind: str, to_kind: str, reason: str) -> None:
    """The routing moved off the preferred backend (breaker trip,
    failed batch, init giving up). Dumps the flight ring — but only on
    an actual active-kind TRANSITION: a flapping device with the breaker
    half-open re-probes repeatedly, and every failed probe lands here;
    one dump per transition bounds the file stream and keeps the hub
    worker thread off the disk (mirrors LoopWatchdog's one-report-per-
    wedge discipline)."""
    BACKEND["fallbacks"] += 1
    transitioned = ACTIVE["kind"] != to_kind
    set_active(to_kind)
    trace.emit("backend", "fallback", from_kind=from_kind, to_kind=to_kind, reason=reason)
    logger.warning("backend fallback %s -> %s: %s", from_kind, to_kind, reason)
    if transitioned:
        trace.auto_dump("backend-fallback")


def record_breaker(state: str) -> None:
    """TPU circuit-breaker state change ("open"/"half-open"/"closed")."""
    BACKEND["breaker_transitions"] += 1
    trace.emit("backend", "breaker", state=state)


def set_active(kind: str) -> None:
    ACTIVE["kind"] = kind


def snapshot() -> dict:
    """JSON-ready view (bench output, /debug endpoints)."""
    lat = sorted(ATTACH_LATENCIES)
    return {
        **{k: v for k, v in BACKEND.items()},
        "attach_latency_s": [round(v, 3) for v in ATTACH_LATENCIES],
        "attach_latency_max_s": round(lat[-1], 3) if lat else 0.0,
        "compile_seconds": {k: round(v, 3) for k, v in COMPILE_SECONDS.items()},
        "compile_cache": dict(COMPILE_CACHE),
        "active_kind": ACTIVE["kind"],
        "mesh": {k: v for k, v in MESH.items()},
        "shard_sigs": dict(SHARD_SIGS),
        "routes": {k: list(v) for k, v in ROUTES.items()},
    }


def reset() -> None:
    """Test hook: clear all process-wide stores."""
    for k in BACKEND:
        BACKEND[k] = 0.0
    for k in MESH:
        MESH[k] = 0.0
    ATTACH_LATENCIES.clear()
    COMPILE_SECONDS.clear()
    COMPILE_CACHE.clear()
    SHARD_SIGS.clear()
    SHARD_DISPATCHES.clear()
    ROUTES.clear()
    ACTIVE["kind"] = "none"
