"""Per-device health registry for the sharded verification mesh.

Dispatch shards over every visible device (crypto/tpu/verify.py), so one
sick chip must not take the whole mesh down. This module keeps one circuit breaker
per device (the libs/retry breaker every other degradation path in the
repo uses):

  * a sharded dispatch failure calls `on_dispatch_failure(exc)`, which
    probes every device in the active set with a tiny bounded kernel and
    trips the breakers of the chips that fail — the mesh degrades to the
    N−1 survivors and the failed shard re-verifies there (verify.py
    re-dispatches; the CPU fallback in crypto/batch.py only takes over
    when the dispatch path keeps failing with no membership change,
    i.e. the whole mesh is effectively dead);
  * a tripped device re-joins through the breaker's half-open protocol:
    after the reset timeout, the next `device_list()` call runs one
    bounded recovery probe and re-admits the chip on success;
  * every membership change lands in `crypto/backend_telemetry` as a
    `record_degrade` transition (flight dump on shrink) so the mesh's
    health history is readable from /metrics and trace dumps.

Env knobs (the TMTPU_MESH_* family):
  TMTPU_MESH_MAX_DEVICES    cap the mesh size (0/unset = all devices)
  TMTPU_MESH_BREAKER_RESET  seconds a tripped device stays out before a
                            recovery probe (default 60)
  TMTPU_MESH_PROBE_TIMEOUT  per-device probe bound, seconds (default 10)
"""

from __future__ import annotations

import logging
import os
import threading

from ...libs.retry import CircuitBreaker

logger = logging.getLogger("crypto.tpu.mesh")

_lock = threading.Lock()
_devices: list | None = None  # all jax devices at first enumeration
_breakers: dict[int, CircuitBreaker] = {}
#: device ids forced unhealthy (tests / chaos injection): probes of
#: these devices always fail, so a forced device trips on the next
#: dispatch failure and stays out until cleared
_forced_failures: set[int] = set()


def _breaker_reset_s() -> float:
    return float(os.environ.get("TMTPU_MESH_BREAKER_RESET", "60"))


def _probe_timeout_s() -> float:
    return float(os.environ.get("TMTPU_MESH_PROBE_TIMEOUT", "10"))


def _max_devices() -> int:
    return int(os.environ.get("TMTPU_MESH_MAX_DEVICES", "0"))


def _enumerate() -> list:
    """All visible devices (cached; callers hold _lock). Safe to call
    only from the device path — jax is already imported and attached."""
    global _devices
    if _devices is None:
        try:
            import jax

            devs = list(jax.devices())
        except Exception as e:  # noqa: BLE001 — backend not up
            logger.debug("device enumeration failed: %r", e)
            return []
        raw_total = len(devs)
        cap = _max_devices()
        if cap > 0:
            devs = devs[:cap]
        _devices = devs
        for d in devs:
            _breakers[d.id] = CircuitBreaker(
                failure_threshold=1,
                reset_timeout=_breaker_reset_s(),
                name=f"mesh-device-{d.id}",
            )
        from .. import backend_telemetry as bt

        # one definition everywhere: total = devices visible to jax,
        # active = devices the dispatch mesh may actually span (capped,
        # breaker-filtered) — batch._probe_tpu records the same split
        bt.record_mesh(raw_total, len(devs))
    return _devices


def _probe_device(dev, timeout_s: float | None = None) -> bool:
    """One tiny bounded computation pinned to `dev`. Runs on a daemon
    thread with a join timeout: a wedged chip must cost bounded time,
    never hang the dispatch path."""
    if dev.id in _forced_failures:
        return False
    res: dict = {}

    def run():
        try:
            import jax
            import numpy as np

            x = jax.device_put(np.arange(8, dtype=np.int32), dev)
            res["ok"] = int((x + 1).sum()) == 36
        except Exception as e:  # noqa: BLE001 — a failed probe is the signal
            res["error"] = e

    t = threading.Thread(target=run, name=f"mesh-probe-{dev.id}", daemon=True)
    t.start()
    t.join(timeout_s if timeout_s is not None else _probe_timeout_s())
    return bool(res.get("ok"))


def device_list() -> list:
    """The active mesh: devices whose breaker is closed, plus any
    tripped device whose half-open window admits a recovery probe that
    passes (re-admission is a recorded degrade transition upward).

    Probes run OUTSIDE the module lock: a wedged chip's probe costs the
    CALLING thread up to the bounded timeout (once per reset window —
    allow() claims the single half-open slot under the lock), but other
    threads selecting kernels or refreshing the hub's mesh size are
    never serialized behind it."""
    from .. import backend_telemetry as bt

    with _lock:
        devs = _enumerate()
        candidates = [
            d for d in devs
            if _breakers[d.id].state != "closed" and _breakers[d.id].allow()
        ]
    recovered = []
    for d in candidates:
        ok = _probe_device(d)
        with _lock:
            if ok:
                _breakers[d.id].record_success()
                recovered.append(d)
            else:
                _breakers[d.id].record_failure()
    with _lock:
        active = [d for d in _enumerate() if _breakers[d.id].state == "closed"]
    if recovered:
        bt.record_degrade(
            len(active) - len(recovered),
            len(active),
            f"recovery probe passed on {[d.id for d in recovered]}",
        )
    return active


def active_count() -> int:
    return len(device_list())


def on_dispatch_failure(exc: BaseException | None = None) -> bool:
    """A sharded dispatch raised: probe every device in the active set,
    trip the breakers of the ones that fail, and record the degrade.
    Returns True when membership changed (the caller re-selects kernels
    on the survivors and retries), False when every probe passed — a
    transient/kernel error, not a chip death: the caller re-raises and
    the ordinary TPU→CPU fallback machinery takes over."""
    from .. import backend_telemetry as bt

    with _lock:
        devs = _enumerate()
        active = [d for d in devs if _breakers[d.id].state == "closed"]
        failed = []
        for d in active:
            if not _probe_device(d):
                _breakers[d.id].record_failure()
                failed.append(d.id)
    if not failed:
        return False
    bt.record_degrade(
        len(active),
        len(active) - len(failed),
        f"dispatch failure {exc!r}; probe failed on {failed}",
    )
    return True


def force_fail(device_id: int, fail: bool = True) -> None:
    """Test/chaos hook: pin a device's probes to failure (or release
    it). Releasing does not close the breaker — the device re-joins
    through the normal half-open recovery probe."""
    with _lock:
        if fail:
            _forced_failures.add(device_id)
        else:
            _forced_failures.discard(device_id)


def reset() -> None:
    """Test hook: forget enumeration, breakers, and forced failures."""
    global _devices
    with _lock:
        _devices = None
        _breakers.clear()
        _forced_failures.clear()
