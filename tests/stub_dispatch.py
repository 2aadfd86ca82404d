"""Stub kernels for tests of what happens AROUND a device dispatch
(`tests/test_tpu_fill.py`, the light client's encode-ahead cases in
`tests/test_light_link.py`): `crypto/tpu/verify`'s dispatch loop runs as it
is, on the suite's CPU devices, and no program is compiled.

The stubs log their dispatch, and the equation's verdict logs when it is
read — the collect — so a test reads the order of dispatch, in-flight work
and collect from the log. The bitmap is the host prep's own s < L column:
a signature whose s half is all 0xff reads False, every other row True
(the equation's verdict is True unless a test builds a `Verdict` itself).
"""

import numpy as np

from tendermint_tpu.crypto import backend_telemetry as bt
from tendermint_tpu.crypto import batch as B
from tendermint_tpu.crypto.tpu import verify as V
from tendermint_tpu.libs.retry import CircuitBreaker


class Verdict:
    """The equation's verdict as the device would hand it back: reading it
    is the collect."""

    def __init__(self, log, value=True, raises=None):
        self.log, self.value, self.raises = log, value, raises

    def __bool__(self):
        self.log.append("collect")
        if self.raises is not None:
            raise self.raises
        return self.value


def install_kernels(monkeypatch, log, max_bucket=64):
    """Single-device stubs in `max_bucket`-row chunks; returns (eq, sig)."""

    def eq(ua, r, ga, rd, zs, sv, gidx):
        log.append("dispatch")
        return np.asarray(sv), Verdict(log)

    def sig(a, r, s, h, sv):
        log.append("attribute")
        return np.asarray(sv)

    monkeypatch.setattr(V, "_shard_devices", lambda: [])
    monkeypatch.setattr(V, "_get_kernel_eq", lambda: eq)
    monkeypatch.setattr(V, "_get_kernel", lambda: sig)
    monkeypatch.setattr(V, "_MAX_BUCKET", max_bucket)
    return eq, sig


def install_device_route(monkeypatch, cutoff=1):
    """`AdaptiveBatchVerifier`'s device route from `cutoff` signatures on, a
    breaker that one failure opens, pristine telemetry (the caller resets
    `backend_telemetry` again when done)."""
    monkeypatch.setattr(B, "_tpu_available", True)
    monkeypatch.setattr(B, "MIN_TPU_BATCH", cutoff)
    monkeypatch.setattr(B, "_tpu_breaker",
                        CircuitBreaker(failure_threshold=1, reset_timeout=30, name="t"))
    bt.reset()
    bt.set_active("tpu")
    return B
