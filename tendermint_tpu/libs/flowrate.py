"""Flow-rate limiting and measurement (reference internal/libs/flowrate —
mikioh/flowrate — as used by MConnection's send/recv monitors,
internal/p2p/conn/connection.go:122-150).

`RateLimiter` is an asyncio token bucket: `await limiter.throttle(n)`
sleeps exactly long enough that the long-run average stays at `rate`
bytes/sec, with up to one `burst` of credit. This is the connection-level
backpressure discipline — senders BLOCK instead of dropping at a full
queue, so a slow peer slows its own stream rather than silently shedding
consensus-critical messages.

`Meter` tracks an exponentially-weighted transfer rate for reporting
(the reference's flowrate.Monitor Status.AvgRate analog).
"""

from __future__ import annotations

import asyncio
import time


class RateLimiter:
    """Token bucket. rate: bytes/sec (0 = unlimited); burst: max bytes of
    accumulated credit (default one second's worth)."""

    def __init__(self, rate: float, burst: float | None = None):
        self.rate = float(rate)
        self.burst = float(burst if burst is not None else rate)
        self._credit = self.burst
        self._last = time.monotonic()
        self._lock = asyncio.Lock()

    async def throttle(self, n: int) -> None:
        """Consume `n` bytes of credit, sleeping until available. Requests
        larger than the burst are allowed but pay the full debt (the
        bucket goes negative) so the AVERAGE still converges to `rate`."""
        if self.rate <= 0 or n <= 0:
            return
        async with self._lock:
            now = time.monotonic()
            self._credit = min(
                self.burst, self._credit + (now - self._last) * self.rate
            )
            self._last = now
            self._credit -= n
            if self._credit < 0:
                await asyncio.sleep(-self._credit / self.rate)

    def would_block(self, n: int) -> bool:
        now = time.monotonic()
        credit = min(self.burst, self._credit + (now - self._last) * self.rate)
        return credit < n


class Meter:
    """EWMA transfer-rate meter (reference flowrate.Monitor)."""

    def __init__(self, window_s: float = 1.0):
        self.window_s = window_s
        self.total = 0
        self._rate = 0.0
        self._last = time.monotonic()

    def update(self, n: int) -> None:
        now = time.monotonic()
        dt = now - self._last
        self.total += n
        if dt > 0:
            alpha = min(1.0, dt / self.window_s)
            inst = n / dt
            self._rate += alpha * (inst - self._rate)
            self._last = now

    @property
    def rate(self) -> float:
        """Bytes/sec, decayed toward zero while idle."""
        now = time.monotonic()
        dt = now - self._last
        if dt > self.window_s * 4:
            return 0.0
        return self._rate
