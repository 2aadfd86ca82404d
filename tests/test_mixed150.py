"""The mixed committee as a deployment (cell `mixed150.sequential`), at a
small size: the benchmark's own `light_mixed` driver drives a seeded chain
of 20 validators (10 ed25519 + 10 secp256k1) through `light.LightClient`
on the DEVICE route of the suite's CPU devices — the Edwards rows of every
range through the equation kernel, the secp256k1 rows down the host lane —
and every number compared equals the plain reference's. Then the same with
the lane broken underneath (it answers True without verifying): honest
traffic reads the same, and `correct` has to come out false.

All cases live in this one file: the 64-row programs (about half a minute
of XLA CPU compile) are compiled once and shared.
"""

import pytest

from benchmark import run
from benchmark.tests import tiny_mixed

SEED = 3000003201


@pytest.fixture
def device_route(monkeypatch):
    """The device route, on the suite's CPU devices, with the cut-off at 1
    (so the trusted-header commit's 7 Edwards rows ride it too, and the
    driver's check reads that from the cut-off) and pristine telemetry."""
    from tendermint_tpu.crypto import backend_telemetry as bt
    from tendermint_tpu.crypto import batch as B
    from tendermint_tpu.libs.retry import CircuitBreaker

    monkeypatch.setattr(B, "_tpu_available", True)
    monkeypatch.setattr(B, "MIN_TPU_BATCH", 1)
    monkeypatch.setattr(B, "_tpu_breaker",
                        CircuitBreaker(failure_threshold=1, reset_timeout=30, name="t"))
    bt.reset()
    bt.set_active("tpu")
    yield B
    bt.reset()


def _lane_answers_true(items):
    return [True] * len(items)


@pytest.mark.parametrize("lane,traced", [("sound", True), ("sound", False),
                                         ("answers-true", False)])
def test_tiny_mixed_cell_on_the_device_route(device_route, monkeypatch, tmp_path, lane, traced):
    if lane != "sound":
        monkeypatch.setattr(device_route, "_verify_slice", _lane_answers_true)
    res = run.execute(tiny_mixed.make_root(str(tmp_path)), tiny_mixed.CELL, SEED, 0.4, traced,
                      device=tiny_mixed.CPU_DEVICE)
    failed = {k for k, c in res["checks"].items() if not c["ok"]}
    checks = {k: c["value"] for k, c in res["checks"].items()}
    # honest traffic reads the same either way: what the lane is for shows
    # only where a signature is bad
    assert checks["verdict_mismatches"] == 0 and checks["stored_mismatches"] == 0
    assert checks["sigs_verified_minus_needed"] == 0
    assert checks["edwards_sigs_on_device_minus_range_needed"] == 0
    assert checks["ecdsa_sigs_on_host_minus_needed"] == 0
    assert checks["warmup_refusal_height_delta.edwards"] == 0
    assert checks["tpu_route_sigs"] >= 1 and res["attempted"] > 0 and res["failed"] == 0
    if lane == "sound":
        assert not failed and res["correct"] is True
    else:
        assert failed == {"warmup_refusal_height_delta.ecdsa"} and res["correct"] is False
    m = {k: v["value"] for k, v in res["metrics"].items()}
    if not traced:
        assert m["light_headers_per_s"] > 0 and m["setup_s"] > 0
        return
    # what a CPU run can read of the cell's per-layer metrics: the spans and
    # counters (no device plane: the trace's shares are left out, never 0)
    assert {"host_lane_ms_per_ksig.mixed", "host_lane_wait_ms_per_header.mixed",
            "host_lane_share.mixed", "edwards_row_share.mixed", "collect_ms_per_ksig.mixed",
            "verify_ms_per_header.mixed", "tpu_resolve_ms_per_ksig.mixed",
            "tpu_prep_ms_per_ksig.mixed", "device_wait_ms_per_dispatch.mixed",
            "device_route_share.mixed", "inline_compiles.mixed",
            # the recorder's CPU readings (PR 37): the work inside the wall twins
            "collect_cpu_ms_per_ksig.mixed", "tpu_prep_cpu_ms_per_ksig.mixed",
            "host_off_cpu_share.mixed", "host_cores_busy.mixed"} == set(m)
    assert 0 < m["collect_cpu_ms_per_ksig.mixed"] <= m["collect_ms_per_ksig.mixed"] + 1e-6
    assert 0 < m["tpu_prep_cpu_ms_per_ksig.mixed"] <= m["tpu_prep_ms_per_ksig.mixed"] + 1e-6
    assert 0 <= m["host_off_cpu_share.mixed"] <= 100 and m["host_cores_busy.mixed"] > 0
    assert m["edwards_row_share.mixed"] == 50.0 and m["device_route_share.mixed"] == 50.0
    assert 0 <= m["host_lane_share.mixed"] <= 100 and m["host_lane_ms_per_ksig.mixed"] > 0


def test_a_lane_that_skips_rows_is_caught_by_its_count(device_route, monkeypatch, tmp_path):
    """The lane verifies, but its route is never counted (as a lane that
    dropped its rows would read): the ECDSA count tells, and the total."""
    from tendermint_tpu.crypto import backend_telemetry as bt

    real = bt.record_route
    monkeypatch.setattr(bt, "record_route",
                        lambda route, n: None if route == "host-ecdsa" else real(route, n))
    res = run.execute(tiny_mixed.make_root(str(tmp_path)), tiny_mixed.CELL, SEED + 1, 0.3, False,
                      device=tiny_mixed.CPU_DEVICE)
    failed = {k for k, c in res["checks"].items() if not c["ok"]}
    assert failed == {"ecdsa_sigs_on_host_minus_needed", "sigs_verified_minus_needed"}
