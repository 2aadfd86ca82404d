"""`verify.while_in_flight`: host work the caller hands the dispatch loop to
do while its chunks are on the device (`tpu.fill`).

The kernels are stubs that log their dispatch and whose equation verdict
logs when it is read (the collect), so the order of dispatch, filler and
collect is what the tests read — on the suite's CPU devices, no program
compiled.
"""

import threading

import numpy as np
import pytest
import stub_dispatch

from tendermint_tpu.crypto import backend_telemetry as bt
from tendermint_tpu.crypto import batch as B
from tendermint_tpu.crypto import verify_hub as vh
from tendermint_tpu.crypto.tpu import verify as V
from tendermint_tpu.libs import trace
from tendermint_tpu.testing import det_priv_keys


class _Dev:
    def __init__(self, i):
        self.id = i


@pytest.fixture
def log():
    return []


@pytest.fixture
def stub(monkeypatch, log):
    """Single-device stub kernels and 64-row chunks: a hundred rows are two."""
    return stub_dispatch.install_kernels(monkeypatch, log, max_bucket=64)


@pytest.fixture
def device_route(monkeypatch, stub):
    yield stub_dispatch.install_device_route(monkeypatch)
    bt.reset()


@pytest.fixture
def recorder():
    was = trace.RECORDER.enabled
    trace.RECORDER.enabled = True
    trace.RECORDER.clear()
    yield trace.RECORDER
    trace.RECORDER.clear()
    trace.RECORDER.enabled = was


def _entries(n, keys=3):
    return [V.ResolvedSig((i % keys).to_bytes(4, "little") + b"\x00" * 28,
                          b"\x01" + b"\x00" * 31, 0, 0) for i in range(n)]


def _items(n, tag=b"fill"):
    keys = det_priv_keys(5)
    out = []
    for i in range(n):
        k, msg = keys[i % 5], tag + b"-%d" % i
        out.append((k.pub_key(), msg, k.sign(msg)))
    return out


def _verifier(items):
    bv = B.AdaptiveBatchVerifier()
    bv.add_many(items)
    return bv


def _fills(recorder):
    return [x["attrs"] for x in recorder.dump()
            if (x["subsystem"], x["name"]) == ("tpu", "fill")]


def _registered():
    return getattr(V._dispatch_local, "work", None)


# -- where it runs ---------------------------------------------------------------


def test_runs_once_after_the_last_dispatch_and_before_the_first_collect(stub, log, recorder):
    with V.while_in_flight(lambda: log.append("fill")) as work:
        out = V.verify_resolved(_entries(100))
        assert work.ran
        # a second dispatch loop under the same registration: not again
        V.verify_resolved(_entries(10))
    assert out.all() and len(out) == 100
    assert log == ["dispatch", "dispatch", "fill", "collect", "collect", "dispatch", "collect"]
    assert _fills(recorder) == [{"chunks": 2, "ran": True}]
    assert _registered() is None


def test_the_filler_span_holds_the_callers_own_spans(stub, recorder):
    def fn():
        with trace.span("light", "encode_ahead", n=1):
            pass

    with V.while_in_flight(fn):
        V.verify_resolved(_entries(10))
    spans = recorder.dump()
    (fill,) = [x for x in spans if x["name"] == "fill"]
    (inner,) = [x for x in spans if x["name"] == "encode_ahead"]
    assert inner["parent_id"] == fill["span_id"]


def test_runs_once_across_a_degrade_retry(monkeypatch, stub, log, recorder):
    """The first chunk's collect raises on a two-chip mesh, the mesh reports
    a dead chip, and the chunk goes out again through `_dispatch_and_collect`
    on what is left: the registration has run by then and stays run."""
    from tendermint_tpu.crypto.tpu import mesh as mesh_mod

    eq, sig = stub
    mesh = [[_Dev(0), _Dev(1)]]

    def sharded_eq(ua, r, ga, rd, zs, sv, gidx):
        log.append("dispatch-sharded")
        return np.asarray(sv), stub_dispatch.Verdict(log, raises=RuntimeError("chip 1 died"))

    def on_failure(exc):
        mesh[0] = []
        return True

    monkeypatch.setenv("TMTPU_FORCE_SHARDED", "1")
    monkeypatch.setattr(V, "_shard_devices", lambda: mesh[0])
    monkeypatch.setattr(V, "_get_sharded", lambda devs: (sharded_eq, sig))
    monkeypatch.setattr(mesh_mod, "on_dispatch_failure", on_failure)
    retries = bt.BACKEND["degrade_retries"]
    with V.while_in_flight(lambda: log.append("fill")):
        out = V.verify_resolved(_entries(40))
    assert out.all() and len(out) == 40
    assert bt.BACKEND["degrade_retries"] == retries + 1
    assert log == ["dispatch-sharded", "fill", "collect", "dispatch", "collect"]
    assert len(_fills(recorder)) == 1


def test_not_when_every_dispatch_raised(monkeypatch, stub, log, recorder):
    def boom(*a):
        raise RuntimeError("enqueue failed")

    monkeypatch.setattr(V, "_get_kernel_eq", lambda: boom)
    with V.while_in_flight(lambda: log.append("fill")) as work:
        with pytest.raises(RuntimeError, match="enqueue failed"):
            V.verify_resolved(_entries(100))
    assert not work.ran and log == [] and _fills(recorder) == []


@pytest.mark.parametrize("why", ["no-device", "below-cutoff", "breaker-open"])
def test_not_on_the_cpu_route(monkeypatch, device_route, log, recorder, why):
    if why == "no-device":
        monkeypatch.setattr(B, "_tpu_available", False)
    elif why == "below-cutoff":
        monkeypatch.setattr(B, "MIN_TPU_BATCH", 1000)
    else:
        B._tpu_breaker.record_failure()
    bv = _verifier(_items(20))
    with V.while_in_flight(lambda: log.append("fill")) as work:
        ok, bitmap = bv.verify()
    assert ok and all(bitmap) and bv.last_route == "cpu"
    assert not work.ran and log == [] and _fills(recorder) == []
    assert _registered() is None


def test_nothing_registered_runs_nothing(device_route, log, recorder):
    bv = _verifier(_items(100))
    ok, _bitmap = bv.verify()
    assert ok and bv.last_route == "tpu"
    assert log == ["dispatch", "dispatch", "collect", "collect"]
    assert _fills(recorder) == []


def test_another_threads_dispatch_does_not_see_it(stub, log, recorder):
    out = []
    with V.while_in_flight(lambda: log.append("fill")) as work:
        t = threading.Thread(target=lambda: out.append(V.verify_resolved(_entries(100))))
        t.start()
        t.join()
    assert out[0].all() and not work.ran
    assert log == ["dispatch", "dispatch", "collect", "collect"] and _fills(recorder) == []


def test_a_hub_dispatches_on_its_runner_and_runs_nothing(device_route, log, recorder):
    """What keeps block-sync and live consensus out of it: the caller's
    registration is its own thread's, the hub's dispatch the runner's."""
    items = _items(100, b"hub")
    hub = vh.acquire_hub(max_batch=128, window_ms=1.0, cache_size=0)
    try:
        with V.while_in_flight(lambda: log.append("fill")) as work:
            assert all(hub.verify_many(items, lane="backfill"))
        assert hub.stats()["dispatched_sigs"] == 100
    finally:
        vh.release_hub()
    assert not work.ran and "fill" not in log and "dispatch" in log
    assert _fills(recorder) == [] and bt.ROUTES["tpu"][1] == 100


# -- a filler that raises ----------------------------------------------------------


def test_a_raising_filler_is_not_a_device_fault(device_route, log, recorder):
    items = _items(100)
    s = items[70][2]
    items[70] = (*items[70][:2], s[:32] + b"\xff" * 32)  # s >= L: False in the stub's bitmap
    ok0, bitmap0 = _verifier(items).verify()
    assert not ok0 and sum(bitmap0) == 99 and not bitmap0[70]
    del log[:]

    class Owed(Exception):
        pass

    def fn():
        log.append("fill")
        raise Owed("encode failed")

    bv = _verifier(items)
    result = []
    with pytest.raises(Owed, match="encode failed"):
        with V.while_in_flight(fn):
            result.append(bv.verify())
            # the body goes on: the error waits for the exit
            log.append("after-verify")
    assert result == [(ok0, bitmap0)] and bv.last_route == "tpu"
    assert log == ["dispatch", "dispatch", "fill", "collect", "collect", "after-verify"]
    assert B._tpu_breaker.state == "closed" and B._tpu_breaker.opens == 0
    assert bt.BACKEND["fallbacks"] == 0 and "cpu-fallback" not in bt.ROUTES
    assert _fills(recorder) == [{"chunks": 2, "ran": False}]
    assert _registered() is None


def test_the_bodys_error_wins_over_the_fillers(stub):
    def fn():
        raise ValueError("filler")

    with pytest.raises(KeyError, match="body"):
        with V.while_in_flight(fn):
            V.verify_resolved(_entries(10))
            raise KeyError("body")
    assert _registered() is None


def test_the_registration_ends_with_the_with(stub, log):
    with V.while_in_flight(lambda: log.append("outer")) as outer:
        with V.while_in_flight(lambda: log.append("inner")) as inner:
            assert _registered() is inner
        assert _registered() is outer
        with pytest.raises(RuntimeError):
            with V.while_in_flight(lambda: log.append("never")):
                raise RuntimeError("body")
        assert _registered() is outer
    assert _registered() is None and log == []
