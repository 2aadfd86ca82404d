"""HashHub — the process-wide SHA-256 chokepoint, in the VerifyHub mold.

Signature verification funnels through `verify_hub`; this module is the
same idea for the OTHER crypto hot loop (ROADMAP's HashHub item): every
hot-path hash — part-set roots, tx Merkle roots, header/app-hash
chains, validator-set hashes, LightD hop hashing — goes through
`sha256_many` / `sha256_one` here instead of calling `hashlib` raw.
The tmtlint `hash-chokepoint` rule enforces the funnel the way
`verify-chokepoint` enforces verifies: crypto/ stays the sink.

Why a chokepoint and not just a batched helper:

  * **Lanes.** Callers tag work as block-build (`LANE_BUILD`), verify
    (`LANE_VERIFY`), or light-hop (`LANE_LIGHT`) — either explicitly
    (`sha256_many(msgs, lane=...)`) or ambiently via `lane_ctx()` for
    deep call chains (the light verifier wraps whole hops). Lanes are
    ACCOUNTING, not priority queues: hashing is synchronous and
    microseconds-scale, so unlike VerifyHub there is no scheduler
    thread — but per-lane batch/occupancy stats tell the perf story
    (`hashhub_*` in /metrics) the same way verifyhub lane stats do.
  * **One breaker, one fallback contract.** The opt-in device route
    (TMTPU_HASH_TPU=1, `crypto/tpu/sha256.py`) sits behind the SAME
    shared TPU breaker as the verify kernels (`crypto/batch`): a wedged
    backend degrades hashing AND verifying to the host at once — they
    share the device — and the degrade costs latency, never
    correctness: any device error re-hashes the same batch inline with
    `hashlib` and returns identical bytes.
  * **Kill switch.** TMTPU_HASHHUB=0 (or `use_hashhub(False)`) restores
    the scalar recursive Merkle paths wholesale — the WireGen adoption
    pattern, see `crypto/merkle.use_hashhub`. This module keeps serving
    `sha256_many` either way (it is just hashlib in a loop then).

The host path IS the fast path on CPU images: one `sha256_many` call
per Merkle tree level replaces O(n) recursive Python frames, which is
where the ≥1.5× at 1024 leaves came from (measured on a CPU host).
The device route only engages for wide buckets when explicitly enabled,
because per-call OpenSSL is ~µs and a cold XLA compile is not.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time

from ..libs import trace
from ..libs.metrics import record_resilience

_sha256 = hashlib.sha256

__all__ = [
    "LANE_BUILD",
    "LANE_VERIFY",
    "LANE_LIGHT",
    "sha256_many",
    "sha256_one",
    "lane_ctx",
    "current_lane",
    "stats_snapshot",
    "reset_stats",
]

LANE_BUILD = "build"
LANE_VERIFY = "verify"
LANE_LIGHT = "light"
_LANES = (LANE_BUILD, LANE_VERIFY, LANE_LIGHT)

#: device route engages only for batches at least this wide — below it
#: even a warm kernel call loses to the hashlib loop (env-tunable the
#: way MIN_TPU_BATCH is for signatures)
MIN_DEVICE_BATCH = int(os.environ.get("TMTPU_MIN_HASH_BATCH", "256"))

#: per-lane and global counters; plain dict with unlocked += on the
#: hot path (bls.STATS precedent — a rare lost increment in a stats
#: counter is acceptable, a lock in the hash loop is not)
STATS = {
    "batches": 0,
    "messages": 0,
    "singles": 0,
    "device_batches": 0,
    "device_messages": 0,
    "fallback_batches": 0,
    "breaker_skips": 0,
    "max_batch": 0,
    "lane_batches": {lane: 0 for lane in _LANES},
    "lane_messages": {lane: 0 for lane in _LANES},
}

class _LaneLocal(threading.local):
    # class attribute = per-thread default WITHOUT the AttributeError
    # machinery `getattr(tls, "lane", default)` pays on every miss
    # (~1µs/call — measurable at merkle tree-level call rates)
    lane = LANE_BUILD


_tls = _LaneLocal()


def current_lane() -> str:
    """The ambient lane set by the innermost `lane_ctx` (LANE_BUILD
    when none is active — proposers build more trees than anyone)."""
    return _tls.lane


class lane_ctx:
    """Ambient lane for a whole call chain, so deep paths (light
    verifier → validator-set hash → merkle → here) tag their hashing
    without threading a kwarg through every layer. Re-entrant; restores
    the previous lane on exit."""

    def __init__(self, lane: str):
        if lane not in _LANES:
            raise ValueError(f"unknown hash lane {lane!r}")
        self._lane = lane
        self._prev = LANE_BUILD

    def __enter__(self) -> "lane_ctx":
        self._prev = _tls.lane
        _tls.lane = self._lane
        return self

    def __exit__(self, *exc) -> None:
        _tls.lane = self._prev


def _host_many(msgs: list[bytes]) -> list[bytes]:
    s = _sha256
    return [s(m).digest() for m in msgs]


#: device-route probe result: None = unprobed, False = unavailable or
#: not opted in, else the crypto.tpu.sha256 module. Cached because the
#: env read + module lookup would otherwise run once per tree LEVEL on
#: the hot path (tests reset via _reset_device_probe)
_device = None


def _device_module():
    global _device
    if _device is None:
        try:
            from .tpu import sha256 as dev

            _device = dev if dev.device_enabled() else False
        except Exception:  # noqa: BLE001 — no backend means host path
            _device = False
    return _device


def _reset_device_probe() -> None:
    """Tests only: re-read TMTPU_HASH_TPU on the next batch."""
    global _device
    _device = None


def _device_route(msgs: list[bytes], lane: str) -> list[bytes] | None:
    """Try the kernel behind the shared TPU breaker. None means the
    caller hashes on the host (breaker open, device failed, or batch
    shape not kernel-eligible) — identical bytes either way."""
    from . import batch as _batch

    dev = _device_module()
    limit = dev.max_device_bytes()
    if any(len(m) > limit for m in msgs):
        return None  # long messages (64 KiB parts) are host work
    if not _batch.tpu_breaker().allow():
        STATS["breaker_skips"] += 1
        record_resilience("hashhub_breaker_skips")
        return None
    try:
        out = dev.sha256_device(msgs)
    except Exception as e:  # noqa: BLE001 — any device error degrades
        from . import backend_telemetry as bt

        _batch.tpu_breaker().record_failure()
        STATS["fallback_batches"] += 1
        record_resilience("hashhub_fallback_batches")
        record_resilience("hashhub_fallback_msgs", len(msgs))
        bt.record_fallback("tpu", "cpu", f"hash: {e!r}")
        return None
    _batch.tpu_breaker().record_success()
    STATS["device_batches"] += 1
    STATS["device_messages"] += len(msgs)
    return out


def sha256_many(msgs: list[bytes], *, lane: str | None = None) -> list[bytes]:
    """Hash a batch of independent messages; THE hot-loop entry point
    (merkle level passes land here — one call per tree level).

    Device-eligible batches (wide enough, short messages, opt-in env)
    route to the JAX kernel behind the shared breaker; everything else
    — and every device failure — is one tight hashlib loop. Bytes are
    identical on every route.

    This function is called once per merkle tree LEVEL, so its fixed
    overhead is the batching win's denominator. Narrow batches (the
    common case — every level of a header or small-block tree) take
    the bottom path: counters, then one tight loop, no clock reads.
    `hash.batch` spans are emitted only for wide batches (>=
    MIN_DEVICE_BATCH): a span per microseconds-scale level would both
    dominate the work it measures and flood the flight-recorder ring
    (which is ON by default), while wide batches are the ones whose
    route/occupancy the trace story actually needs."""
    n = len(msgs)
    if not n:
        return []
    if lane is None:
        lane = _tls.lane
    st = STATS
    st["batches"] += 1
    st["messages"] += n
    if n > st["max_batch"]:
        st["max_batch"] = n
    st["lane_batches"][lane] += 1
    st["lane_messages"][lane] += n
    if n >= MIN_DEVICE_BATCH:
        t0 = time.monotonic()
        out = None
        route = "cpu"
        if _device_module():
            out = _device_route(msgs, lane)
            if out is not None:
                route = "tpu"
        if out is None:
            out = _host_many(msgs)
        if trace.is_enabled():
            trace.emit(
                "hash",
                "batch",
                duration_s=time.monotonic() - t0,
                n=n,
                lane=lane,
                route=route,
            )
        return out
    s = _sha256
    return [s(m).digest() for m in msgs]


def sha256_one(data: bytes, *, lane: str | None = None) -> bytes:
    """Single-message funnel for hot paths with nothing to batch
    (mempool tx keys, indexer keys, event ids). Inline hashlib — the
    point is the accounting and the lint-visible chokepoint, not a
    device trip for one digest."""
    STATS["singles"] += 1
    STATS["lane_messages"][lane if lane is not None else current_lane()] += 1
    return _sha256(data).digest()


def stats_snapshot() -> dict:
    """Copy for /metrics folding (`libs/metrics._fold_hashhub`)."""
    snap = {k: v for k, v in STATS.items() if not isinstance(v, dict)}
    snap["lane_batches"] = dict(STATS["lane_batches"])
    snap["lane_messages"] = dict(STATS["lane_messages"])
    return snap


def reset_stats() -> None:
    """Tests only."""
    for k, v in STATS.items():
        if isinstance(v, dict):
            for lane in v:
                v[lane] = 0
        else:
            STATS[k] = 0
